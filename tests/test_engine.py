"""Engine correctness vs sequential numpy oracles (paper Section IV-B)."""
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import reference as ref
from repro.core.engine import EngineConfig
from repro.core.graph import CSRGraph, rmat_edges


def small_cfg(**kw):
    base = dict(f_pop=8, r_pop=8, u_pop=16, max_t2=8, cap_route_range=8,
                cap_route_update=32, cap_rangeq=128, cap_updq=2048,
                max_rounds=5000)
    base.update(kw)
    return EngineConfig(**base)


def make_graph(scale=8, seed=0, ef=6):
    n, src, dst, val = rmat_edges(scale, edge_factor=ef, seed=seed)
    return CSRGraph.from_edges(n, src, dst, val)


@pytest.fixture(scope="module")
def g():
    return make_graph()


@pytest.fixture(scope="module")
def pg(g):
    return alg.prepare(g, T=4)


def pick_root(g):
    deg = g.ptr[1:] - g.ptr[:-1]
    return int(np.argmax(deg))


def test_bfs_matches_reference(g, pg):
    root = pick_root(g)
    res = alg.bfs(pg, root, small_cfg())
    expect = ref.bfs_ref(g, root)
    np.testing.assert_array_equal(res.values, expect)
    assert int(res.stats.drops) == 0


def test_sssp_matches_reference(g, pg):
    root = pick_root(g)
    res = alg.sssp(pg, root, small_cfg())
    expect = ref.sssp_ref(g, root)
    finite = np.isfinite(expect)
    assert (np.isfinite(res.values) == finite).all()
    np.testing.assert_allclose(res.values[finite], expect[finite], rtol=1e-5)
    assert int(res.stats.drops) == 0


def test_wcc_matches_reference(g):
    gs = alg.symmetrize(g)
    pg = alg.prepare(gs, T=4)
    res = alg.wcc(pg, small_cfg())
    expect = ref.wcc_ref(gs)
    np.testing.assert_array_equal(res.values, expect)


def test_spmv_matches_reference(g, pg):
    rng = np.random.default_rng(1)
    x = rng.normal(size=g.num_vertices).astype(np.float32)
    res = alg.spmv(pg, x, small_cfg())
    expect = ref.spmv_ref(g, x.astype(np.float64))
    np.testing.assert_allclose(res.values, expect, rtol=2e-4, atol=1e-4)


def test_pagerank_matches_reference(g, pg):
    res = alg.pagerank(pg, iters=8, cfg=small_cfg())
    expect = ref.pagerank_ref(g, iters=8)
    np.testing.assert_allclose(res.values, expect, rtol=2e-3, atol=1e-7)


def test_bsp_mode_matches_and_needs_more_rounds(g, pg):
    root = pick_root(g)
    res_async = alg.bfs(pg, root, small_cfg(mode="async"))
    res_bsp = alg.bfs(pg, root, small_cfg(mode="bsp"))
    np.testing.assert_array_equal(res_async.values, res_bsp.values)
    # removing the barrier should never be slower (paper Fig. 5 last rung)
    assert int(res_async.stats.rounds) <= int(res_bsp.stats.rounds)
    assert int(res_bsp.stats.epochs) >= 1


def test_static_policy_correct_but_spillier(g, pg):
    root = pick_root(g)
    res_t = alg.bfs(pg, root, small_cfg(policy="traffic"))
    res_s = alg.bfs(pg, root, small_cfg(policy="static"))
    np.testing.assert_array_equal(res_t.values, res_s.values)
    assert int(res_s.stats.drops) == 0


def test_high_order_placement_correct(g):
    pg2 = alg.prepare(g, T=4, scheme="high_order")
    root = pick_root(g)
    res = alg.bfs(pg2, root, small_cfg())
    np.testing.assert_array_equal(res.values, ref.bfs_ref(g, root))


def test_vertex_aligned_edges_correct(g):
    pg3 = alg.prepare(g, T=4, edge_mode="vertex_aligned")
    root = pick_root(g)
    res = alg.bfs(pg3, root, small_cfg())
    np.testing.assert_array_equal(res.values, ref.bfs_ref(g, root))


def _naive_bfs(g, root):
    """The per-edge loop BFS that ``bfs_ref`` replaced, kept as its
    reference: one frontier vertex and one edge at a time."""
    dist = np.full(g.num_vertices, np.inf)
    dist[root] = 0
    frontier, d = [root], 0
    while frontier:
        nxt = []
        for v in frontier:
            for e in range(g.ptr[v], g.ptr[v + 1]):
                u = g.dst[e]
                if dist[u] == np.inf:
                    dist[u] = d + 1
                    nxt.append(u)
        frontier, d = nxt, d + 1
    return dist


@pytest.mark.parametrize("seed", range(4))
def test_bfs_ref_matches_naive_bfs(seed):
    """The level-synchronous oracle equals the loop BFS on random small
    graphs, directed and symmetrized, from roots of every kind
    (isolated, high-degree, arbitrary)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 200))
    m = int(rng.integers(0, 4 * n))
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    g = CSRGraph.from_edges(n, src, dst)
    for graph in (g, alg.symmetrize(g)):
        deg = graph.ptr[1:] - graph.ptr[:-1]
        for root in {0, int(np.argmax(deg)), int(rng.integers(0, n))}:
            np.testing.assert_array_equal(ref.bfs_ref(graph, root),
                                          _naive_bfs(graph, root))


@pytest.mark.parametrize("driver", ["algorithms", "multi_source",
                                    "frontend_continuous"])
def test_cut_run_raises(g, pg, driver):
    """A run that max_rounds stops with work still pending is an error in
    every host driver, never a partial result."""
    from repro.serve import Frontend, multi_source
    root = pick_root(g)
    cfg = small_cfg(max_rounds=3)
    with pytest.raises(RuntimeError, match="max_rounds=3"):
        if driver == "algorithms":
            alg.bfs(pg, root, cfg)
        elif driver == "multi_source":
            multi_source(pg, "bfs", [root, -1], cfg)
        else:
            Frontend(pg, cfg=cfg, width=2,
                     policy="continuous").serve([root, root])
    # the same drivers finish normally with room to spare
    assert int(alg.bfs(pg, root, small_cfg()).stats.rounds) > 3


def tight_cfg(**kw):
    """An update queue of exactly one round's worst-case inflow, and an
    update fabric far narrower than the edge scans' output, so the update
    spill queue fills within a few rounds."""
    base = dict(f_pop=32, r_pop=32, u_pop=8, max_t2=16, cap_route_range=16,
                cap_route_update=4, cap_rangeq=128, **kw)
    return EngineConfig(**base, cap_updq=EngineConfig(**base).min_caps(4)[1])


@pytest.fixture(scope="module")
def sym_g():
    """Symmetrized and unweighted, as Graph500 BFS reads it."""
    n, src, dst, _ = rmat_edges(10, edge_factor=8, seed=0)
    return CSRGraph.from_edges(n, np.concatenate([src, dst]),
                               np.concatenate([dst, src]))


@pytest.fixture(scope="module")
def sym_pg(sym_g):
    return alg.prepare(sym_g, T=4)


def without_credit_stop(monkeypatch):
    """The TSU without its credit stop: no queue ever counts as short of
    room for a round's inflow (only the local throttle is left)."""
    from repro.core.program import Program
    monkeypatch.setattr(Program, "round_inflow",
                        lambda self, cfg, T: (0,) * len(self.channels))


def test_credit_stop_keeps_tight_queues_from_dropping(sym_g, sym_pg,
                                                      monkeypatch):
    from repro.core.engine import BFS
    root = pick_root(sym_g)
    res = alg.bfs(sym_pg, root, tight_cfg(max_rounds=5001))
    np.testing.assert_array_equal(res.values, ref.bfs_ref(sym_g, root))
    assert int(res.stats.drops) == 0
    # the same budgets overflow the update queue without the stop
    without_credit_stop(monkeypatch)
    value, frontier = alg.init_min_state(sym_pg, [root])
    out = alg.local_engine_call(sym_pg, BFS, tight_cfg(max_rounds=5002),
                                value, frontier)
    assert int(out[2].drops) > 0


@pytest.mark.parametrize("driver,max_rounds", [
    ("algorithms", 5003), ("multi_source", 5004),
    ("frontend_continuous", 5005)])
def test_dropped_run_raises(sym_g, sym_pg, monkeypatch, driver, max_rounds):
    """A run that dropped messages is an error in every host driver,
    never a wrong result."""
    from repro.serve import Frontend, multi_source
    without_credit_stop(monkeypatch)
    root = pick_root(sym_g)
    cfg = tight_cfg(max_rounds=max_rounds)  # a fresh trace per driver
    with pytest.raises(RuntimeError, match="dropped"):
        if driver == "algorithms":
            alg.bfs(sym_pg, root, cfg)
        elif driver == "multi_source":
            multi_source(sym_pg, "bfs", [root, -1], cfg)
        else:
            Frontend(sym_pg, cfg=cfg, width=2,
                     policy="continuous").serve([root, root])
