"""SPMD correctness of the Dalorex LM islands (routed embedding, MoE
dispatch, pipeline) on 8 forced CPU devices — subprocess, like test_spmd."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.embedding import embed_lookup, place_table
    from repro.core.moe import (moe_block, moe_dense_oracle,
                                to_dispatch_layout)
    from repro.parallel.sharding import (SINGLE_POD_RULES, mesh_context)

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = SINGLE_POD_RULES

    # ---- routed embedding == plain gather ----
    V, d, B, S = 64, 16, 4, 32
    M = 4
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    table = jax.random.normal(ks[0], (V, d), jnp.float32)
    ids = jax.random.randint(ks[1], (B, S), 0, V, jnp.int32)
    placed = jnp.asarray(place_table(np.asarray(table), M))
    with mesh_context(mesh, rules):
        def f(t, i):
            emb, ovf = embed_lookup(t, i, routed=True,
                                    capacity_factor=4.0)
            return emb, ovf
        t_sh = jax.device_put(placed, NamedSharding(mesh, P("model", None)))
        i_sh = jax.device_put(ids, NamedSharding(mesh, P("data", "model")))
        emb, ovf = jax.jit(f)(t_sh, i_sh)
    assert int(ovf) == 0, int(ovf)
    # oracle: plain gather from the UNPLACED table, using placed ids:
    # placed[(v % M)*chunk + v//M] = table[v]
    expect = np.asarray(table)[np.asarray(ids)]
    np.testing.assert_allclose(np.asarray(emb), expect, rtol=1e-6, atol=1e-6)
    print("EMB-OK")

    # ---- routed-embedding gradient flows to the right rows ----
    with mesh_context(mesh, rules):
        def loss(t):
            emb, _ = embed_lookup(t, i_sh, routed=True, capacity_factor=4.0)
            return (emb ** 2).sum()
        g = jax.jit(jax.grad(loss))(t_sh)
    g_np = np.asarray(g)
    # oracle grad: 2*table[v] summed per occurrence, scattered to placed rows
    expect_g = np.zeros_like(g_np)
    chunk = V // M
    for v in np.asarray(ids).ravel():
        p = (v % M) * chunk + v // M
        expect_g[p] += 2 * np.asarray(table)[v]
    np.testing.assert_allclose(g_np, expect_g, rtol=1e-5, atol=1e-5)
    print("EMB-GRAD-OK")

    # ---- MoE dispatch (E > M: eps=2) == dense oracle ----
    E, k, dm, ff = 8, 2, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    oracle_params = {
        "router": jax.random.normal(ks[0], (dm, E)) * 0.1,
        "w_gate": jax.random.normal(ks[1], (E, dm, ff)) * 0.1,
        "w_up": jax.random.normal(ks[2], (E, dm, ff)) * 0.1,
        "w_down": jax.random.normal(ks[3], (E, ff, dm)) * 0.1,
    }
    x = jax.random.normal(ks[4], (4, 32, dm))
    disp = to_dispatch_layout(oracle_params, E, 4)
    with mesh_context(mesh, rules):
        y, aux, ovf = jax.jit(lambda p, xx: moe_block(
            p, xx, E=E, k=k, ff=ff, mlp="swiglu",
            capacity_factor=8.0))(disp, x)
    y_ref, aux_ref = moe_dense_oracle(oracle_params, x, E=E, k=k, ff=ff,
                                      mlp="swiglu")
    assert int(ovf) == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    print("MOE-EPS-OK")

    # ---- MoE dispatch (E < M: expert-TP, tp=2) == dense oracle ----
    E2 = 2
    op2 = {
        "router": jax.random.normal(ks[0], (dm, E2)) * 0.1,
        "w_gate": oracle_params["w_gate"][:E2],
        "w_up": oracle_params["w_up"][:E2],
        "w_down": oracle_params["w_down"][:E2],
    }
    disp2 = to_dispatch_layout(op2, E2, 4)
    with mesh_context(mesh, rules):
        y2, _, ovf2 = jax.jit(lambda p, xx: moe_block(
            p, xx, E=E2, k=1, ff=ff, mlp="swiglu",
            capacity_factor=8.0))(disp2, x)
    y2_ref, _ = moe_dense_oracle(op2, x, E=E2, k=1, ff=ff, mlp="swiglu")
    assert int(ovf2) == 0
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y2_ref),
                               rtol=2e-4, atol=2e-4)
    print("MOE-TP-OK")

    # ---- pipeline over 8 stages == sequential ----
    from repro.parallel.pipeline import pipeline_apply
    pmesh = jax.make_mesh((8,), ("stage",),
                          axis_types=(jax.sharding.AxisType.Auto,))
    n_st, n_micro, mb, dd = 8, 16, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    w = jax.random.normal(ks[0], (n_st, dd, dd)) * 0.3
    xs = jax.random.normal(ks[1], (n_micro, mb, dd))
    stage = lambda wi, xx: jnp.tanh(xx @ wi)
    y_pipe = jax.jit(lambda w, xs: pipeline_apply(
        stage, w, xs, mesh=pmesh, axis="stage", n_micro=n_micro))(w, xs)
    y_seq = xs
    for i in range(n_st):
        y_seq = jax.vmap(lambda xx: stage(w[i], xx))(y_seq)
    np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_seq),
                               rtol=2e-5, atol=2e-5)
    print("PIPE-OK")

    # pipeline is differentiable
    gfn = jax.jit(jax.grad(lambda w: pipeline_apply(
        stage, w, xs, mesh=pmesh, axis="stage",
        n_micro=n_micro).sum()))
    gw = gfn(w)
    gseq = jax.grad(lambda w: _seq(w))(w) if False else None
    def seq_loss(w):
        y = xs
        for i in range(n_st):
            y = jax.vmap(lambda xx: stage(w[i], xx))(y)
        return y.sum()
    gw_ref = jax.grad(seq_loss)(w)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               rtol=2e-4, atol=2e-4)
    print("PIPE-GRAD-OK")
""")


@pytest.mark.slow
def test_spmd_islands():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-5000:]
    for tag in ("EMB-OK", "EMB-GRAD-OK", "MOE-EPS-OK", "MOE-TP-OK",
                "PIPE-OK", "PIPE-GRAD-OK"):
        assert tag in out.stdout, (tag, out.stdout)


RING_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.layers import blockwise_attention
from repro.parallel.ring import ring_attention

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
for (B, S, H, Hkv, hd, win) in [(2, 64, 4, 2, 16, 0), (2, 64, 4, 4, 16, 24),
                                (4, 128, 2, 1, 32, 0)]:
    ks = jax.random.split(jax.random.PRNGKey(S + hd), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, Hkv, hd))
    v = jax.random.normal(ks[2], (B, S, Hkv, hd))
    ref = blockwise_attention(
        q, jnp.repeat(k, H // Hkv, 2), jnp.repeat(v, H // Hkv, 2),
        jnp.arange(S), window=win)
    ring = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh=mesh, batch_axes=("data",), window=win))(q, k, v)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    # gradients flow through the ring (ppermute transpose)
    g = jax.jit(jax.grad(lambda q: ring_attention(
        q, k, v, mesh=mesh, batch_axes=("data",), window=win).sum()))(q)
    gr = jax.grad(lambda q: blockwise_attention(
        q, jnp.repeat(k, H // Hkv, 2), jnp.repeat(v, H // Hkv, 2),
        jnp.arange(S), window=win).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=2e-3, atol=2e-3)
print("RING-OK")
"""


@pytest.mark.slow
def test_ring_attention_matches_blockwise():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", RING_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-5000:]
    assert "RING-OK" in out.stdout
