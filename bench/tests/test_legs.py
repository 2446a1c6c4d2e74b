"""``bench/legs.py``: device time per round leg and the program's host
spans, from the trace the harness loads.

A synthetic trace checks the arithmetic exactly; an engine call recorded
here on the CPU checks that the program's leg map names every operation
the profiler sees, and that the legs add up to the device's busy time;
``profile_cell`` runs both cells' traced engine call on a small graph."""
import jax
import numpy as np
import pytest

from bench import legs, run
from bench import trace as tr
from bench.tests.test_trace import synthetic
from repro.core import algorithms as alg
from repro.core import engine


@pytest.fixture(scope="module", autouse=True)
def own_executables():
    """Key the compilation cache on the programs' metadata, and forget the
    executables of this process: JAX's cache strips the metadata from its
    key, so an executable cached for the same program without its scopes
    (a parent commit's) would be loaded, and it names no leg."""
    was = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.clear_caches()
    yield
    jax.config.update("jax_compilation_cache_include_metadata_in_key", was)
    jax.clear_caches()


def test_names_match_the_program():
    assert legs.LEGS == engine.ROUND_LEGS
    assert legs.HOST_SPANS == alg.HOST_SPANS
    # the harness's own spans that the program now emits too
    assert set(run.SPANS) & set(legs.HOST_SPANS) == {"engine_call",
                                                     "to_original"}


@pytest.mark.parametrize("rows,shares", [
    ([], []),
    ([(0, 10), (2, 4), (5, 8)], [5, 2, 3]),              # nested: own time
    ([(0, 10), (5, 15)], [5, 10]),                       # overlap: later wins
    ([(0, 10), (0, 10)], [0, 10]),
    ([(0, 10), (2, 4), (3, 8)], [4, 1, 5]),              # inner ones overlap
    ([(30, 40), (0, 10), (5, 12)], [10, 5, 7]),          # unsorted, a gap
])
def test_shares_split_the_union(rows, shares):
    s = np.array([r[0] for r in rows], np.int64)
    e = np.array([r[1] for r in rows], np.int64)
    assert legs.share_ns(s, e).tolist() == shares
    us, ue = tr.union(s, e)
    assert sum(shares) == int((ue - us).sum())


def test_nested_shares_are_the_own_time_of_the_top_operations():
    s = np.array([0, 10, 30, 35, 60], np.int64)       # a loop and its body
    e = np.array([100, 20, 50, 40, 70], np.int64)
    assert legs.share_ns(s, e).tolist() == tr.self_ns(s, e).tolist()


def test_leg_seconds_of_the_synthetic_trace_exactly():
    """Device 0: fusion.1 100-120 and 140-150 (the all-to-all started
    last, so 120-135 is its), sort.2 180-190 outside the map; fusion.9
    lies before the window."""
    t = synthetic()
    got = legs.leg_seconds(t, {"fusion.1": "scan", "all-to-all.3": "route",
                               "fusion.9": "init"})
    assert got == {"scan": pytest.approx(30e-9, abs=1e-18),
                   "route": pytest.approx(15e-9, abs=1e-18),
                   "unmapped": pytest.approx(10e-9, abs=1e-18)}
    assert sum(got.values()) == pytest.approx(tr.reduce(t)["first_busy_s"],
                                              abs=1e-18)
    with pytest.raises(ValueError):
        legs.leg_seconds(tr.Trace(t.devices, []), {})


def test_span_seconds_inside_the_window():
    t = synthetic()
    t.spans += [("epoch_update", 150, 158), ("epoch_update", 90, 99)]
    assert legs.span_seconds(t, "epoch_update") == [
        pytest.approx(8e-9, abs=1e-18)]
    assert legs.span_seconds(t, "engine_call") == [
        pytest.approx(60e-9, abs=1e-18), pytest.approx(24e-9, abs=1e-18)]


def test_legs_of_an_engine_call_recorded_here(tmp_path):
    """A BFS on the CPU under the profiler: the leg map names every
    operation of the window, and the legs sum to the busy time."""
    from jax.profiler import TraceAnnotation

    from repro.core.graph import CSRGraph, rmat_edges

    n, src, dst, val = rmat_edges(7, edge_factor=5, seed=3)
    pg = alg.prepare(alg.symmetrize(CSRGraph.from_edges(n, src, dst, val)),
                     T=4)
    cfg = engine.EngineConfig(f_pop=8, r_pop=8, u_pop=16, max_t2=8,
                              cap_route_range=8, cap_route_update=32,
                              cap_rangeq=128, cap_updq=4096)
    alg.bfs(pg, 0, cfg)
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("window"):
        alg.bfs(pg, 0, cfg)
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path), ("window",) + legs.HOST_SPANS)
    leg_s = legs.leg_seconds(t, alg.engine_leg_map(pg, engine.BFS, cfg))
    assert set(leg_s) <= set(alg.ENGINE_SCOPES) | {"unscoped", "unmapped"}
    assert set(leg_s) >= set(legs.LEGS)
    busy = tr.reduce(t)["first_busy_s"]
    assert sum(leg_s.values()) == pytest.approx(busy, rel=1e-9)
    assert leg_s.get("unscoped", 0) + leg_s.get("unmapped", 0) \
        <= 0.02 * busy


@pytest.mark.parametrize("workload", ["kron17-bfs", "kron17-pr"])
def test_profile_cell_reduces_the_traced_call_by_leg(workload):
    cell, config, traffic = run.resolve(run.load_benchmark(), workload)
    config = dict(config, scale=8, tiles=4)
    out = legs.profile_cell(cell, config, traffic, 11, jax.devices())
    assert set(out["leg_ms"]) >= set(legs.LEGS)
    assert all(out["leg_ms"][leg] > 0 for leg in legs.LEGS)
    assert sum(out["leg_ms"].values()) == pytest.approx(out["round_ms"],
                                                        rel=1e-9)
    assert out["stray_share"] <= 0.02
    assert all(leg in legs.LEGS for _, _, leg in out["device_ops"])
    spans = out["host_span_ms"]
    assert spans["engine_call"] and spans["engine_wait"]
    # the gaps between engine calls carry the program's spans
    assert {lab for lab, _ in out["idle_gaps"]} <= set(
        legs.HOST_SPANS) | set(run.SPANS)
    if workload == "kron17-pr":
        assert len(spans["epoch_update"]) == 1
    else:
        assert spans["to_original"] and spans["init_state"]
