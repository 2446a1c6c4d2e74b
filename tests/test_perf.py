"""The cycle/energy performance model (repro.perf).

Pins down the model's contract:

* per-link class attribution: torus wraparounds and ruche express
  channels are priced differently from local neighbor hops;
* cycles are monotone non-decreasing in rounds (each round costs at
  least ``t_round``);
* on a fixed no-spill workload the fabric ordering holds:
  ideal <= mesh <= torus-with-wrap-penalty;
* the accumulated energy reconciles exactly (f32 rounding aside) with
  the linear formula over the final Stats counters — including under
  heavy spilling, where the replay terms dominate;
* ``stats_row`` surfaces every channel (``msgs_<i>``) with the legacy
  range/update keys as first/last views;
* fig6's ``speedup_vs_linear`` no longer depends on the order of the
  ``tiles`` argument (the unsorted-tiles bug).

The SPMD == LocalComm bit-for-bit check for the new Stats fields lives in
tests/test_spmd.py (subprocess, 8 CPU devices).
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..")))

from repro.core import algorithms as alg
from repro.core.engine import EngineConfig, Stats
from repro.core.graph import CSRGraph, rmat_edges
from repro.noc import (N_CHANNELS, LOCAL_BWD, LOCAL_FWD, RUCHE_BWD,
                       RUCHE_FWD, Mesh2D, Ruche, Torus2D, make_network)
from repro.perf import (CLASS_LOCAL, CLASS_PORT, CLASS_RUCHE, CLASS_WRAP,
                        PerfParams, energy_from_totals)


def small_cfg(**kw):
    base = dict(f_pop=8, r_pop=8, u_pop=16, max_t2=8, cap_route_range=8,
                cap_route_update=32, cap_rangeq=128, cap_updq=2048,
                max_rounds=20000)
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def g():
    n, src, dst, val = rmat_edges(7, edge_factor=5, seed=5)
    return CSRGraph.from_edges(n, src, dst, val)


@pytest.fixture(scope="module")
def pg(g):
    return alg.prepare(g, T=8)


def root_of(g):
    return int(np.argmax(g.ptr[1:] - g.ptr[:-1]))


# --------------------------------------------------------------------------
# Link-class attribution.
# --------------------------------------------------------------------------

def test_link_classes_price_wrap_and_ruche_differently():
    mesh = Mesh2D(8, 2, 4)
    torus = Torus2D(8, 2, 4)
    ruche = Ruche(8, 2, 4, ruche_factor=2)
    for net in (mesh, torus, ruche):
        assert net.link_classes.shape == (net.num_links,)
    # mesh: local and (never-used) ruche channels only, no wraps
    assert not (mesh.link_classes == CLASS_WRAP).any()
    # torus: each of the `rows` row lines closes its ring with one wrap
    # link per direction, same for the `cols` column lines
    tc = torus.link_classes
    assert (tc == CLASS_WRAP).sum() == 2 * (torus.rows + torus.cols)
    # the wrap links sit exactly where line_usage charges a wraparound:
    # forward at the end of the line, backward at position 0
    xb = tc[:N_CHANNELS * torus.rows * torus.cols].reshape(
        torus.rows, N_CHANNELS, torus.cols)
    assert (xb[:, LOCAL_FWD, -1] == CLASS_WRAP).all()
    assert (xb[:, LOCAL_BWD, 0] == CLASS_WRAP).all()
    assert (xb[:, LOCAL_FWD, :-1] == CLASS_LOCAL).all()
    # ruche express channels are their own class on every backend
    rc = ruche.link_classes.reshape(-1)
    rx = rc[:N_CHANNELS * 8].reshape(2, N_CHANNELS, 4)
    assert (rx[:, RUCHE_FWD] == CLASS_RUCHE).all()
    assert (rx[:, RUCHE_BWD] == CLASS_RUCHE).all()
    # ideal crossbar ports: no wire latency, switch energy only
    ideal = make_network(small_cfg(noc="ideal"), 8)
    assert (ideal.link_classes == CLASS_PORT).all()
    assert PerfParams().hop_cycle_table()[CLASS_PORT] == 0


# --------------------------------------------------------------------------
# Cycle accumulator.
# --------------------------------------------------------------------------

def test_cycles_monotone_in_rounds(g, pg):
    root = root_of(g)
    prev = -1.0
    full = alg.bfs(pg, root, small_cfg())
    value, frontier = alg.init_min_state(pg, [root])
    for r in (2, 5):
        # runs cut at max_rounds: the engine call itself, since the host
        # drivers refuse to return a cut run's partial values
        _, _, stats, _, pending = alg.local_engine_call(
            pg, alg.BFS, small_cfg(max_rounds=r), value, frontier)
        assert int(pending) > 0
        assert int(stats.rounds) == r
        cyc = float(np.asarray(stats.cycles))
        # every round costs at least t_round, so more rounds = more cycles
        assert cyc >= prev + (1 if prev >= 0 else 0)
        assert cyc >= float(stats.rounds)  # t_round=1 floor
        prev = cyc
    assert float(full.stats.cycles) > prev
    assert float(full.stats.energy_pj) > 0


def test_fabric_cycle_ordering_ideal_mesh_torus(g):
    """On a no-spill fixed workload the wire terms order the fabrics:
    the perfect crossbar adds nothing, the mesh pays local hops, and a
    torus with a punitive wraparound cost pays the most (its shorter-way
    routes concentrate traffic on the expensive wrap links)."""
    pg = alg.prepare(g, T=8)
    root = root_of(g)
    penal = PerfParams(t_hop_wrap=8)
    cyc, rounds = {}, {}
    for noc in ("ideal", "mesh", "torus"):
        cfg = small_cfg(noc=noc, cap_route_range=64, cap_route_update=256,
                        cap_rangeq=1024, cap_updq=8192, perf=penal)
        s = alg.bfs(pg, root, cfg).stats
        assert int(np.asarray(s.spills).sum()) == 0  # apples to apples
        cyc[noc] = float(np.asarray(s.cycles))
        rounds[noc] = int(s.rounds)
    assert rounds["ideal"] == rounds["mesh"] == rounds["torus"]
    assert cyc["ideal"] <= cyc["mesh"] <= cyc["torus"], cyc


# --------------------------------------------------------------------------
# Energy accounting.
# --------------------------------------------------------------------------

@pytest.mark.parametrize("noc,link_cap", [("ideal", 0), ("mesh", 2)])
def test_energy_reconciles_with_stats_totals(g, pg, noc, link_cap):
    root = root_of(g)
    cfg = small_cfg(noc=noc, link_cap=link_cap)
    s = alg.bfs(pg, root, cfg).stats
    if noc == "mesh":
        assert int(np.asarray(s.spills).sum()) > 0  # replay term exercised
    got = float(np.asarray(s.energy_pj))
    want = energy_from_totals(s, cfg.perf, make_network(cfg, pg.T), pg.T)
    assert got == pytest.approx(want, rel=1e-4)


def test_params_are_overridable_and_scale_cost(g, pg):
    root = root_of(g)
    base = alg.bfs(pg, root, small_cfg()).stats
    slow = alg.bfs(pg, root, small_cfg(
        perf=PerfParams(t_sram=8, t_alu=4, e_pop=10.0))).stats
    assert int(slow.rounds) == int(base.rounds)
    assert float(slow.cycles) > float(base.cycles)
    assert float(slow.energy_pj) > float(base.energy_pj)


# --------------------------------------------------------------------------
# Benchmark plumbing: per-channel stats_row keys, fig6 tiles ordering.
# --------------------------------------------------------------------------

def test_stats_row_emits_every_channel():
    from benchmarks.common import stats_row
    import jax.numpy as jnp
    s4 = Stats.zero(num_links=4, max_hops=2, num_channels=4)._replace(
        msgs=jnp.asarray([10, 20, 30, 40], jnp.int32))
    row = stats_row(s4)
    assert [row[f"msgs_{i}"] for i in range(4)] == [10, 20, 30, 40]
    assert "msgs_4" not in row
    assert row["msgs_range"] == 10 and row["msgs_update"] == 40
    assert row["msgs_sum"] == 100 and row["msgs_max"] == 40
    # 1-channel program: the legacy keys alias the same (only) channel
    s1 = Stats.zero(num_channels=1)._replace(
        msgs=jnp.asarray([7], jnp.int32))
    row1 = stats_row(s1)
    assert row1["msgs_0"] == row1["msgs_range"] == row1["msgs_update"] == 7
    # model scalars come through as floats
    assert isinstance(row["cycles"], float)
    assert isinstance(row["energy_pj"], float)


def test_fig6_speedup_invariant_to_tiles_order():
    from benchmarks import fig6_scaling
    rows = fig6_scaling.run(scale=6, tiles=(16, 4))
    assert [r["T"] for r in rows] == [4, 16]  # sorted before use
    assert rows[0]["speedup_vs_linear"] == 1.0  # normalized to smallest T
    assert all(r["cycles"] > 0 and r["energy_pj"] > 0 for r in rows)
    assert all(r["time_model_s"] > 0 and r["gteps"] > 0 for r in rows)
    with pytest.raises(AssertionError, match="duplicate"):
        fig6_scaling.run(scale=6, tiles=(4, 4))
