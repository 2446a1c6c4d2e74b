"""Pallas tile-grid backend == XLA backend, bit for bit.

Two layers of evidence (DESIGN.md "Pallas backend"):

* kernel-level — each :mod:`repro.kernels.engine` kernel against its XLA
  twin in ``core/program.py`` / ``core/queues.py`` over widths, ragged
  tails, empty frontiers, overflow, and duplicate indices (interpret mode);
* engine-level — ``EngineConfig(backend="pallas")`` against
  ``backend="xla"``: values AND the full Stats tuple (rounds, per-channel
  msgs/spills, cycles, energy_pj, link telemetry) must be equal.  Tier-1
  keeps a representative subset; the full seven-workloads x four-NoCs
  sweep and the shard_map SPMD twin run under ``-m slow`` (as CI does).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import reference as ref
from repro.core.engine import EngineConfig
from repro.core.graph import CSRGraph, rmat_edges
from repro.core.program import Ctx, take_first_k
from repro.core.queues import queue_make, queue_push, queue_take_front
from repro.kernels.engine import (edge_scan_gather, fold_scatter,
                                  frontier_pop, queue_push_pop)

pytestmark = pytest.mark.pallas

INF32 = np.float32(np.finfo(np.float32).max)


def small_cfg(**kw):
    base = dict(f_pop=8, r_pop=8, u_pop=16, max_t2=8, cap_route_range=8,
                cap_route_update=32, cap_rangeq=128, cap_updq=4096,
                max_rounds=20000)
    base.update(kw)
    return EngineConfig(**base)


# --------------------------------------------------------------------------
# Kernel-level: each Pallas kernel vs its XLA twin.
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,k,k_max", [
    (8, 3, 8), (32, 0, 8), (32, 8, 8),   # partial / zero / exact budget
    (257, 100, 16),                      # clamped to k_max (engine contract)
    (64, 5, 16), (16, 16, 16),           # odd width / full pop
])
def test_frontier_pop_matches_take_first_k(n, k, k_max):
    rng = np.random.default_rng(n * 31 + k)
    for density in (0.0, 0.3, 1.0):          # empty / sparse / full frontier
        mask = jnp.asarray(rng.random(n) < density)
        k_dyn = jnp.int32(min(k, k_max))
        i1, v1, m1 = take_first_k(mask, k_dyn, k_max)
        i2, v2, m2 = frontier_pop(mask, k_dyn, k_max)
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
        # idx agrees wherever valid; invalid slots are don't-cares
        np.testing.assert_array_equal(np.where(v1, i1, 0),
                                      np.where(v2, i2, 0))


def test_frontier_pop_vmapped_tile_grid():
    """Under vmap (LocalComm's per-tile stage), the batching rule turns the
    tile axis into the Pallas grid — per-tile results stay identical."""
    rng = np.random.default_rng(0)
    masks = jnp.asarray(rng.random((5, 48)) < 0.25)
    ks = jnp.asarray([0, 1, 4, 8, 8], jnp.int32)
    a = jax.vmap(lambda m, k: frontier_pop(m, k, 8))(masks, ks)
    b = jax.vmap(lambda m, k: take_first_k(m, k, 8))(masks, ks)
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b[2]))


@pytest.mark.parametrize("cap,w,nrows,pop,max_n,prefill", [
    (16, 3, 8, 4, 6, 14),    # near-full: push overflows -> drops
    (8, 2, 8, 8, 8, 0),      # empty queue, pop the whole fresh batch
    (8, 2, 6, 3, 4, 7),      # ragged: pop less than occupancy
    (32, 4, 1, 0, 8, 3),     # zero pop budget (TSU throttled the channel)
])
def test_queue_push_pop_fuses_push_then_take_front(cap, w, nrows, pop,
                                                   max_n, prefill):
    rng = np.random.default_rng(cap * 7 + nrows)
    q = queue_make(cap, w)
    pre = jnp.asarray(rng.integers(0, 99, (cap, w)), jnp.int32)
    q, _ = queue_push(q, pre, jnp.arange(cap) < prefill)
    rows = jnp.asarray(rng.integers(0, 99, (nrows, w)), jnp.int32)
    valid = jnp.asarray(rng.random(nrows) < 0.7)
    q1, d1 = queue_push(q, rows, valid)
    t1, tv1, q1 = queue_take_front(q1, jnp.int32(pop), max_n)
    t2, tv2, ndata, ncount, d2 = queue_push_pop(
        q.data, q.count, rows, valid, jnp.int32(pop), max_n)
    # the engine feeds the FULL taken buffer to the channel transform, so
    # even the garbage rows beyond the pop count must match
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_array_equal(np.asarray(tv1), np.asarray(tv2))
    assert int(d1) == int(d2)
    assert int(q1.count) == int(ncount)
    c = int(ncount)  # live rows identical; rows >= count are unobservable
    np.testing.assert_array_equal(np.asarray(q1.data)[:c],
                                  np.asarray(ndata)[:c])


@pytest.mark.parametrize("e_chunk,r,max_t2", [(64, 10, 8), (128, 1, 16),
                                              (33, 24, 4)])
def test_edge_scan_gather_matches_inline(e_chunk, r, max_t2):
    rng = np.random.default_rng(e_chunk + r)
    ed = jnp.asarray(rng.integers(-1, 100, e_chunk), jnp.int32)
    ev = jnp.asarray(rng.random(e_chunk), jnp.float32)
    start = jnp.asarray(rng.integers(0, 4 * e_chunk, r), jnp.int32)
    # ragged tails: lengths 0..max_t2, some rows invalid
    stop = start + jnp.asarray(rng.integers(0, max_t2 + 1, r), jnp.int32)
    rv = jnp.asarray(rng.random(r) < 0.75)
    nb, w, jv = edge_scan_gather(ed, ev, start, stop, rv, max_t2)
    length = jnp.where(rv, stop - start, 0)
    local0 = jnp.where(rv, start % e_chunk, 0)
    j = jnp.arange(max_t2, dtype=jnp.int32)[None, :]
    eidx = jnp.minimum(local0[:, None] + j, e_chunk - 1)
    jv_ref = rv[:, None] & (j < length[:, None])
    nb_ref = ed[eidx]
    np.testing.assert_array_equal(np.asarray(nb), np.asarray(nb_ref))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(ev[eidx]))
    np.testing.assert_array_equal(np.asarray(jv),
                                  np.asarray(jv_ref & (nb_ref >= 0)))


@pytest.mark.parametrize("op", ["min", "add"])
@pytest.mark.parametrize("v_chunk,r", [(32, 20), (8, 64), (128, 1)])
def test_fold_scatter_matches_xla_twin(op, v_chunk, r):
    rng = np.random.default_rng(v_chunk * 3 + r)
    tgt = jnp.asarray(
        np.where(rng.random(v_chunk) < 0.3, INF32,  # "unreached" sentinels
                 rng.random(v_chunk).astype(np.float32)))
    # heavy duplicates + the v_chunk trash slot for invalid rows
    lidx_raw = jnp.asarray(rng.integers(0, max(v_chunk // 4, 1), r),
                           jnp.int32)
    valid = jnp.asarray(rng.random(r) < 0.6)
    lidx = jnp.where(valid, lidx_raw, v_chunk)
    vals = jnp.asarray(rng.normal(size=r), jnp.float32)
    from repro.core.program import scatter_fold
    ctx_x = Ctx(small_cfg(), 1, 1, v_chunk, "xla")
    ctx_p = Ctx(small_cfg(), 1, 1, v_chunk, "pallas")
    a = scatter_fold(ctx_x, tgt, lidx, vals, valid, op)
    b = scatter_fold(ctx_p, tgt, lidx, vals, valid, op)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fold_scatter_all_invalid_is_identity():
    tgt = jnp.asarray(np.float32([1.0, INF32, 3.0, 4.0]))
    lidx = jnp.full((6,), 4, jnp.int32)  # all trash
    out = fold_scatter(tgt, lidx, jnp.ones((6,), jnp.float32),
                       jnp.zeros((6,), bool), op="min")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(tgt))


# --------------------------------------------------------------------------
# Engine-level: backend="pallas" == backend="xla", full Stats tuple.
# --------------------------------------------------------------------------

def assert_stats_identical(a, b, where=""):
    for f, x, y in zip(a._fields, a, b):
        if f == "launches":
            continue  # launch accounting differs across backends by design
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"Stats.{f} differs between backends {where}")


@pytest.fixture(scope="module")
def g():
    n, src, dst, val = rmat_edges(6, edge_factor=5, seed=1)
    return CSRGraph.from_edges(n, src, dst, val)


@pytest.fixture(scope="module")
def pg(g):
    return alg.prepare(g, T=4)


def run_app(app, g, pg, cfg):
    if app == "bfs":
        root = int(np.argmax(g.ptr[1:] - g.ptr[:-1]))
        return alg.bfs(pg, root, cfg)
    if app == "sssp":
        root = int(np.argmax(g.ptr[1:] - g.ptr[:-1]))
        return alg.sssp(pg, root, cfg)
    if app == "spmv":
        x = np.linspace(0.5, 1.5, g.num_vertices).astype(np.float32)
        return alg.spmv(pg, x, cfg)
    if app == "pagerank":
        return alg.pagerank(pg, iters=2, cfg=cfg)
    raise ValueError(app)


@pytest.mark.parametrize("app,noc", [("spmv", "ideal"), ("bfs", "torus")])
def test_backend_bit_identity_tier1(g, pg, app, noc):
    """Representative tier-1 corners: an add-fold single-epoch workload on
    the crossbar, and a min-fold relaxation on a wrapped physical NoC with
    finite links (spill/replay exercised on the pallas queue kernel)."""
    kw = dict(noc=noc, link_cap=2) if noc != "ideal" else dict(noc=noc)
    rx = run_app(app, g, pg, small_cfg(backend="xla", **kw))
    rp = run_app(app, g, pg, small_cfg(backend="pallas", **kw))
    np.testing.assert_array_equal(rx.values, rp.values)
    assert_stats_identical(rx.stats, rp.stats, f"({app}, {noc})")
    assert int(rp.stats.drops) == 0


def test_backend_empty_frontier(pg):
    """A root with no out-edges drains immediately on both backends."""
    g_iso = CSRGraph.from_edges(8, np.array([0]), np.array([1]),
                                np.ones(1, np.float32))
    pgi = alg.prepare(g_iso, T=4)
    rx = alg.bfs(pgi, 7, small_cfg(backend="xla"))
    rp = alg.bfs(pgi, 7, small_cfg(backend="pallas"))
    np.testing.assert_array_equal(rx.values, rp.values)
    assert_stats_identical(rx.stats, rp.stats, "(empty frontier)")


def test_per_channel_backend_hint_mixes_backends(g, pg):
    """A TaskSpec.backend="xla" pin on the fold channel under a global
    pallas config still matches the all-xla run bit for bit — mixed
    backends compose because every leg is bit-identical."""
    import dataclasses
    from repro.core.program import classic_program, BFS
    prog = classic_program(BFS)
    pinned = dataclasses.replace(
        prog, channels=(prog.channels[0],
                        dataclasses.replace(prog.channels[1],
                                            backend="xla")))
    root = int(np.argmax(g.ptr[1:] - g.ptr[:-1]))
    from repro.core.algorithms import init_min_state, local_engine_call
    value, frontier = init_min_state(pg, [root])
    vx, _, sx, _, _ = local_engine_call(pg, prog, small_cfg(backend="xla"),
                                        value, frontier)
    vm, _, sm, _, _ = local_engine_call(pg, pinned,
                                        small_cfg(backend="pallas"),
                                        value, frontier)
    np.testing.assert_array_equal(np.asarray(vx), np.asarray(vm))
    assert_stats_identical(sx, sm, "(mixed backends)")


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_interpret_mode_follows_the_platform(pg, platform, monkeypatch):
    """The interpreter runs only on the CPU.  Off it, a pallas-backed
    program is refused at config time, naming what the TPU compiler
    refuses, while the xla backend validates as before."""
    from repro.core.program import as_program
    from repro.kernels.engine import interpret_mode
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert interpret_mode() == (platform == "cpu")
    prog = as_program(alg.BFS)
    prog.validate(small_cfg(backend="xla"), pg.T, pg.e_chunk, pg.v_chunk)
    if platform == "cpu":
        prog.validate(small_cfg(backend="pallas"), pg.T, pg.e_chunk,
                      pg.v_chunk)
        return
    with pytest.raises(NotImplementedError,
                       match="(?s)'tpu'.*cumsum.*scatter-min.*2D gather"):
        prog.validate(small_cfg(backend="pallas"), pg.T, pg.e_chunk,
                      pg.v_chunk)


# --------------------------------------------------------------------------
# The full acceptance sweep: seven workloads x four NoCs (slow; CI runs it
# explicitly with -m slow, as for the other multi-minute suites).
# --------------------------------------------------------------------------

APPS = ("bfs", "sssp", "wcc", "spmv", "pagerank", "kcore", "triangles")
NOCS = ("ideal", "mesh", "torus", "ruche")


@pytest.mark.slow
@pytest.mark.parametrize("noc", NOCS)
def test_backend_bit_identity_full_sweep(g, noc):
    gs = alg.symmetrize(g)
    pg = alg.prepare(g, T=4)
    pgs = alg.prepare(gs, T=4)
    pgt = alg.prepare_triangles(gs, T=4)
    root = int(np.argmax(g.ptr[1:] - g.ptr[:-1]))
    kw = dict(noc=noc) if noc == "ideal" else dict(noc=noc, link_cap=2)
    cx, cp = small_cfg(backend="xla", **kw), small_cfg(backend="pallas",
                                                       **kw)
    x = np.linspace(0.5, 1.5, g.num_vertices).astype(np.float32)
    runs = {
        "bfs": lambda c: alg.bfs(pg, root, c),
        "sssp": lambda c: alg.sssp(pg, root, c),
        "wcc": lambda c: alg.wcc(pgs, c),
        "spmv": lambda c: alg.spmv(pg, x, c),
        "pagerank": lambda c: alg.pagerank(pg, iters=2, cfg=c),
        "kcore": lambda c: alg.kcore(pgs, 2, c),
        "triangles": lambda c: alg.triangles(pgt, c),
    }
    for app in APPS:
        rx, rp = runs[app](cx), runs[app](cp)
        np.testing.assert_array_equal(rx.values, rp.values,
                                      err_msg=f"values ({app}, {noc})")
        assert_stats_identical(rx.stats, rp.stats, f"({app}, {noc})")
        assert int(rp.stats.drops) == 0


# --------------------------------------------------------------------------
# SPMD: the pallas backend under real shard_map (subprocess: multi-device
# CPU needs XLA_FLAGS before jax initializes).
# --------------------------------------------------------------------------

SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from repro.core import algorithms as alg
    from repro.core import reference as ref
    from repro.core.engine import EngineConfig
    from repro.core.graph import CSRGraph, rmat_edges

    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((8,), ("x",))
    n, src, dst, val = rmat_edges(7, edge_factor=5, seed=3)
    g = CSRGraph.from_edges(n, src, dst, val)
    pg = alg.prepare(g, T=8)
    cfg = EngineConfig(f_pop=8, r_pop=8, u_pop=16, max_t2=8,
                       cap_route_range=8, cap_route_update=32,
                       cap_rangeq=128, cap_updq=4096, max_rounds=5000,
                       backend="pallas")
    root = int(np.argmax(g.ptr[1:] - g.ptr[:-1]))
    r_spmd = alg.bfs(pg, root, cfg, mesh=mesh)
    r_local = alg.bfs(pg, root, cfg)
    np.testing.assert_array_equal(r_spmd.values, r_local.values)
    np.testing.assert_array_equal(r_spmd.values, ref.bfs_ref(g, root))
    assert int(r_spmd.stats.rounds) == int(r_local.stats.rounds)
    assert float(r_spmd.stats.cycles) == float(r_local.stats.cycles)
    assert float(r_spmd.stats.energy_pj) == float(r_local.stats.energy_pj)
    assert int(r_spmd.stats.drops) == 0
    print("SPMD-PALLAS-OK")
""")


@pytest.mark.slow
def test_spmd_pallas_backend_matches_local():
    env = dict(os.environ)
    src_dir = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                           "src"))
    env["PYTHONPATH"] = src_dir + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", SPMD_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr
    assert "SPMD-PALLAS-OK" in out.stdout
