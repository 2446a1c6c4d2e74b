"""PR7 fused round legs: ONE ``pallas_call`` per channel leg.

Twin-sweep evidence on top of test_backend_pallas.py's kernel/engine
layers:

* harness-level — ``fused_leg_call`` runs an arbitrary staged function as
  exactly one launch (measured via the trace-time tally, not assumed),
  round-trips scalar / zero-size / mixed-dtype pytree leaves, and is
  bit-identical with ``pad_lanes=True`` ((8,128) lane-tile padding);
* engine-level — ``pallas_fuse=True`` (the default) vs the legacy
  ``pallas_fuse=False`` four-kernel path vs xla: values AND the full
  Stats tuple (minus ``launches``, backend-dependent by design) across
  ragged tails, empty frontiers, finite-link spill/replay and
  duplicate-index add folds;
* launch accounting — pinned counts: the classic program runs 3
  launches/round fused (one per leg) vs 5 unfused, triangles' 4-channel
  chain runs 5/round fused; xla runs 0.  Serving lanes (B>1 vmap) keep
  the same per-trace count.
* degenerate queue — ``queue_push_pop`` with cap-0 data takes the
  explicit early-out (no launch) and matches the XLA twin's empty-slice
  semantics.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core.engine import EngineConfig
from repro.core.graph import CSRGraph, rmat_edges
from repro.core.queues import queue_make, queue_push, queue_take_front
from repro.kernels.engine import fused_leg_call, queue_push_pop, tally

pytestmark = pytest.mark.pallas


def small_cfg(**kw):
    base = dict(f_pop=8, r_pop=8, u_pop=16, max_t2=8, cap_route_range=8,
                cap_route_update=32, cap_rangeq=128, cap_updq=4096,
                max_rounds=20000)
    base.update(kw)
    return EngineConfig(**base)


# --------------------------------------------------------------------------
# Harness-level: fused_leg_call is one launch and a faithful pytree wrapper.
# --------------------------------------------------------------------------

def _staged(scalars, arrays):
    """A stage-shaped function: tuple-of-tuples in, mixed dtypes, a scalar
    and a zero-size leaf on both sides."""
    k, flag = scalars
    m, z, f = arrays
    out = jnp.where(flag, m + k, m - k)
    return (out.sum(), (out, z[:0], f * 2.0))


def _staged_args():
    rng = np.random.default_rng(7)
    scalars = (jnp.int32(3), jnp.asarray(True))
    arrays = (jnp.asarray(rng.integers(0, 9, (5, 7)), jnp.int32),
              jnp.zeros((0, 4), jnp.float32),
              jnp.asarray(rng.random(13), jnp.float32))
    return scalars, arrays


@pytest.mark.parametrize("pad_lanes", [False, True])
def test_fused_leg_call_single_launch_bit_identical(pad_lanes):
    scalars, arrays = _staged_args()
    want = jax.jit(_staged)(scalars, arrays)
    with tally() as t:
        got = jax.jit(lambda s, a: fused_leg_call(
            _staged, s, a, pad_lanes=pad_lanes))(
                scalars, arrays)
    assert t.n == 1, "a fused leg must be exactly ONE pallas_call"
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_leg_call_under_vmap_stays_one_launch():
    """LocalComm batches the per-tile stage with vmap: the fused leg must
    stay a single (gridded) launch and match the unbatched results."""
    rng = np.random.default_rng(1)
    xs = jnp.asarray(rng.random((4, 6)), jnp.float32)
    fn = lambda x: (x * 2 + 1, x.sum())
    with tally() as t:
        got = jax.vmap(lambda x: fused_leg_call(fn, x))(xs)
    assert t.n == 1
    want = jax.vmap(fn)(xs)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# Degenerate queue: cap-0 early-out.
# --------------------------------------------------------------------------

def test_queue_push_pop_cap0_matches_xla_and_skips_launch():
    rows = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    valid = jnp.asarray([True, False, True])
    q = queue_make(0, 2)
    q1, d1 = queue_push(q, rows, valid)
    t1, tv1, q1 = queue_take_front(q1, jnp.int32(2), 4)
    with tally() as t:
        t2, tv2, ndata, ncount, d2 = queue_push_pop(
            q.data, q.count, rows, valid, jnp.int32(2), 4)
    assert t.n == 0, "cap-0 early-out must not dispatch a kernel"
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_array_equal(np.asarray(tv1), np.asarray(tv2))
    assert t2.shape[0] == 0 and ndata.shape[0] == 0
    assert int(d1) == int(d2) == 2   # every valid row dropped
    assert int(q1.count) == int(ncount) == 0


# --------------------------------------------------------------------------
# Engine-level: fused == nofuse == xla, plus pinned launch counts.
# --------------------------------------------------------------------------

def assert_stats_identical(a, b, where=""):
    for f, x, y in zip(a._fields, a, b):
        if f == "launches":
            continue  # backend-dependent by design
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"Stats.{f} differs {where}")


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
    if app == "spmv":
        x = np.linspace(0.5, 1.5, g.num_vertices).astype(np.float32)
        return alg.spmv(pg, x, cfg)
    if app == "pagerank":
        return alg.pagerank(pg, iters=2, cfg=cfg)
    raise ValueError(app)


@pytest.mark.parametrize("app,noc", [
    ("bfs", "torus"),      # min fold + finite links: spill/replay in-leg
    ("spmv", "ideal"),     # add fold, duplicate indices, single epoch
    ("pagerank", "ideal"),  # multi-epoch add fold
])
def test_fused_twin_sweep_with_pinned_launch_counts(g, pg, app, noc):
    kw = dict(noc=noc, link_cap=2) if noc != "ideal" else dict(noc=noc)
    rx = run_app(app, g, pg, small_cfg(backend="xla", **kw))
    rn = run_app(app, g, pg, small_cfg(backend="pallas",
                                       pallas_fuse=False, **kw))
    rf = run_app(app, g, pg, small_cfg(backend="pallas", **kw))
    np.testing.assert_array_equal(rx.values, rn.values)
    np.testing.assert_array_equal(rx.values, rf.values)
    assert_stats_identical(rx.stats, rn.stats, f"(nofuse, {app}, {noc})")
    assert_stats_identical(rx.stats, rf.stats, f"(fused, {app}, {noc})")
    assert int(rf.stats.drops) == 0
    # the launch-accounting pins (classic program: K=2 channels -> 3 legs)
    rounds = int(rf.stats.rounds)
    assert int(rx.stats.launches) == 0
    assert int(rf.stats.launches) == 3 * rounds, \
        "fused classic leg must be exactly ONE launch per leg"
    assert int(rn.stats.launches) == 5 * rounds
    assert rounds == int(rx.stats.rounds)


def test_empty_frontier_fused(pg):
    g_iso = CSRGraph.from_edges(8, np.array([0]), np.array([1]),
                                np.ones(1, np.float32))
    pgi = alg.prepare(g_iso, T=4)
    rx = alg.bfs(pgi, 7, small_cfg(backend="xla"))
    rf = alg.bfs(pgi, 7, small_cfg(backend="pallas"))
    np.testing.assert_array_equal(rx.values, rf.values)
    assert_stats_identical(rx.stats, rf.stats, "(empty frontier, fused)")
    assert int(rf.stats.launches) == 3 * int(rf.stats.rounds)


def test_pad_lanes_engine_bit_identical(g, pg):
    """(8,128) lane-tile padding changes the kernel block shapes only —
    values, Stats AND the launch count stay identical."""
    rx = run_app("bfs", g, pg, small_cfg(backend="xla"))
    rf = run_app("bfs", g, pg, small_cfg(backend="pallas"))
    rp = run_app("bfs", g, pg, small_cfg(backend="pallas",
                                         pallas_pad_lanes=True))
    np.testing.assert_array_equal(rx.values, rp.values)
    assert_stats_identical(rx.stats, rp.stats, "(pad_lanes)")
    assert int(rp.stats.launches) == int(rf.stats.launches) > 0


def test_serving_lanes_fused_matches_xla(g, pg):
    """B=3 batched query lanes (vmap over the lane axis on top of the tile
    vmap): the fused leg still matches xla per lane, bit for bit."""
    from repro.serve import multi_source
    deg = np.asarray(g.ptr[1:] - g.ptr[:-1])
    srcs = np.argsort(-deg)[:3].astype(np.int64)
    bx = multi_source(pg, "bfs", srcs, small_cfg(backend="xla"))
    bf = multi_source(pg, "bfs", srcs, small_cfg(backend="pallas"))
    np.testing.assert_array_equal(np.asarray(bx.values),
                                  np.asarray(bf.values))
    np.testing.assert_array_equal(np.asarray(bx.stats.rounds),
                                  np.asarray(bf.stats.rounds))
    assert not np.asarray(bx.stats.launches).any()
    assert np.asarray(bf.stats.launches).sum() > 0


# --------------------------------------------------------------------------
# Deep chain: triangles' 4-channel program -> 5 legs -> 5 launches/round.
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_triangles_fused_launch_count(g):
    gs = alg.symmetrize(g)
    pgt = alg.prepare_triangles(gs, T=4)
    rx = alg.triangles(pgt, small_cfg(backend="xla"))
    rf = alg.triangles(pgt, small_cfg(backend="pallas"))
    np.testing.assert_array_equal(rx.values, rf.values)
    assert_stats_identical(rx.stats, rf.stats, "(triangles, fused)")
    assert int(rf.stats.launches) == 5 * int(rf.stats.rounds)
