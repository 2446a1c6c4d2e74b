"""Cross-layer integration: kernels vs engine semantics, embedding overflow
telemetry, elastic restore across device counts."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.comm import LocalComm
from repro.core.routing import route_tasks
from repro.kernels.scatter_update.kernel import scatter_segments


def test_routed_updates_feed_scatter_kernel():
    """The engine's T3 fold == the Pallas scatter kernel on the same binned
    updates (the kernel is the TPU hot-spot version of the same step)."""
    T, chunk, cap = 4, 64, 32
    rng = np.random.default_rng(0)
    n = 24
    idx = rng.integers(0, T * chunk, (T, n))
    vals = rng.normal(size=(T, n)).astype(np.float32)
    msgs = jnp.stack([jnp.asarray(idx, jnp.int32),
                      jax.lax.bitcast_convert_type(
                          jnp.asarray(vals), jnp.int32)], axis=2)
    dest = jnp.asarray(idx // chunk, jnp.int32)
    comm = LocalComm(T)
    r = route_tasks(comm, msgs, jnp.ones((T, n), bool), dest, cap)
    # per-device binned updates -> local indices
    recv_idx = np.asarray(r.recv[..., 0])
    recv_val = np.asarray(
        jax.lax.bitcast_convert_type(r.recv[..., 1], jnp.float32))
    local_idx = np.where(np.asarray(r.recv_valid), recv_idx % chunk, -1)
    base = rng.normal(size=(T, chunk)).astype(np.float32)
    got = np.asarray(scatter_segments(
        jnp.asarray(base), jnp.asarray(local_idx, jnp.int32),
        jnp.asarray(recv_val), op="min"))
    # oracle: apply all (sent) updates directly
    expect = base.copy()
    spillv = np.asarray(r.spill_valid)
    for t in range(T):
        for i in range(n):
            if not spillv[t, i]:
                d, l = idx[t, i] // chunk, idx[t, i] % chunk
                expect[d, l] = min(expect[d, l], vals[t, i])
    np.testing.assert_allclose(got, expect, rtol=1e-6, atol=1e-6)


def test_embedding_overflow_counter():
    """Capacity starvation is counted, not silent (single-device path uses
    plain gather, so test the routed slot math directly)."""
    from repro.core.embedding import _routed_lookup_local

    # emulate one shard of M=1 so the all_to_all is the identity
    class FakeAxis:
        pass
    # _routed_lookup_local needs an axis; run under a 1-device shard_map
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    from jax.sharding import PartitionSpec as P
    table = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    ids = jnp.zeros((6,), jnp.int32)  # all hit row 0 -> overflow beyond cap

    def body(t, i):
        return _routed_lookup_local(t, i, capacity=2, axis="model", M=1)

    emb, ovf = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(None, None), P(None)),
        out_specs=(P(None, None), P()), check_vma=False))(table, ids)
    assert int(ovf) == 4  # 6 lookups, capacity 2
    np.testing.assert_allclose(np.asarray(emb[:2]),
                               np.asarray(table[:1]).repeat(2, 0))
    assert (np.asarray(emb[2:]) == 0).all()  # overflowed rows zero-filled


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
import sys, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import store

mode, d = sys.argv[1], sys.argv[2]
mesh = jax.make_mesh((len(jax.devices()),), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
tree = {"w": jnp.arange(32, dtype=jnp.float32).reshape(8, 4)}
sh = {"w": NamedSharding(mesh, P("data", None))}
if mode == "save":
    t = jax.tree.map(lambda a, s: jax.device_put(a, s), tree, sh)
    store.save(d, 1, t)
    print("SAVED", len(jax.devices()))
else:
    got = store.restore(d, 1, tree, shardings=sh)
    np.testing.assert_array_equal(np.asarray(got["w"]),
                                  np.asarray(tree["w"]))
    print("RESTORED", len(jax.devices()))
"""


@pytest.mark.slow
def test_elastic_restore_across_device_counts(tmp_path):
    """Checkpoint written on an 8-device mesh restores onto a 2-device mesh
    (the elastic re-scale path)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    d = str(tmp_path / "elastic")
    for ndev, mode, expect in ((8, "save", "SAVED 8"),
                               (2, "restore", "RESTORED 2")):
        out = subprocess.run(
            [sys.executable, "-c", ELASTIC_SCRIPT % ndev, mode, d],
            env=env, capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-3000:]
        assert expect in out.stdout
