"""Graph analytics end to end: all five paper workloads, both placements,
both sync modes, on a LiveJournal-like synthetic (heavy-tailed RMAT) —
the paper's Section V evaluation in miniature — plus a NoC-topology
comparison (ideal crossbar vs mesh vs torus vs ruche) showing the
per-link telemetry of the pluggable fabric (paper Fig. 9), and the two
task-graph workloads (k-core peeling, 2-hop triangle counting) that the
generic task-program executor opens beyond the fixed T1/T2/T3 pipeline.

  PYTHONPATH=src python examples/graph_analytics.py [--scale 12]
      [--preset rmat-hier] [--backend pallas] [--noc hier]
      [--ndies-y 2 --ndies-x 2] [--placement low_order_dielocal]
      [--queries 32]

``--queries N`` appends the serving section: N BFS/SSSP sources batched
through the engine as query lanes (src/repro/serve/), with a queries/sec
line per app.

``--preset`` pulls scale/tiles/edge-factor/backend/noc/ndies/placement
from ``repro.configs.dalorex_graph.PRESETS``; explicit flags override it.
``--backend pallas`` runs every engine call on the tile-grid kernels
(bit-identical results; interpret mode on CPU).  ``--noc hier`` runs the
workload table on the multi-die fabric (``--ndies-y x --ndies-x`` dies);
a ``*_dielocal`` ``--placement`` keeps graph partitions die-resident.
The NoC ablation table always includes the hier rows with their
die-crossing fraction.
"""
import argparse
import functools

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.dalorex_graph import PRESETS
from repro.core import algorithms as alg
from repro.core import reference as ref
from repro.core.engine import EngineConfig as _EngineConfig
from repro.core.graph import CSRGraph, rmat_edges


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default=None)
    ap.add_argument("--scale", type=int, default=None)
    ap.add_argument("--tiles", type=int, default=None)
    ap.add_argument("--backend", choices=("xla", "pallas"), default=None)
    ap.add_argument("--noc", default=None,
                    choices=("ideal", "mesh", "torus", "ruche", "hier"))
    ap.add_argument("--ndies-y", type=int, default=None)
    ap.add_argument("--ndies-x", type=int, default=None)
    ap.add_argument("--placement", default=None,
                    choices=("low_order", "high_order",
                             "low_order_dielocal", "high_order_dielocal"))
    ap.add_argument("--edge-space", choices=("vmem", "hbm"), default=None,
                    help="memory space of the per-tile edge shard "
                         "(repro.mem): hbm streams it through "
                         "double-buffered segment DMA, bit-identical "
                         "values (triangles stays on its pinned vmem "
                         "shard)")
    ap.add_argument("--queries", type=int, default=0,
                    help="also serve N batched multi-source BFS/SSSP "
                         "queries (the repro.serve query lanes) and print "
                         "a queries/sec line")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="run the BFS row with the flight recorder on "
                         "(repro.trace), write the Chrome/Perfetto trace "
                         "JSON to PATH and print the utilization summary "
                         "(results stay bit-identical; see DESIGN.md "
                         "'Tracing & observability')")
    args = ap.parse_args()
    enable_compile_cache()
    wl = PRESETS[args.preset] if args.preset else None
    scale = args.scale if args.scale is not None else \
        (wl.scale if wl else 11)
    tiles = args.tiles if args.tiles is not None else \
        (wl.tiles if wl else 16)
    backend = args.backend if args.backend is not None else \
        (wl.backend if wl else "xla")
    noc = args.noc if args.noc is not None else (wl.noc if wl else "ideal")
    ndies = (args.ndies_y if args.ndies_y is not None else
             (wl.ndies[0] if wl else 1),
             args.ndies_x if args.ndies_x is not None else
             (wl.ndies[1] if wl else 1))
    placement = args.placement if args.placement is not None else \
        (wl.placement if wl else "low_order")
    dies = ndies if placement.endswith("_dielocal") else None
    ef = wl.edge_factor if wl else 10
    edge_space = args.edge_space if args.edge_space is not None else \
        (wl.edge_space if wl else "vmem")
    hbm_window = wl.hbm_window if wl else 0
    cfg_kw = dict(backend=backend, noc=noc, ndies_y=ndies[0],
                  ndies_x=ndies[1], edge_space=edge_space,
                  hbm_window=hbm_window,
                  adapt=wl.adapt if wl else False,
                  adapt_every=wl.adapt_every if wl else 4,
                  adapt_budget=wl.adapt_budget if wl else 64)
    # size the queues from the engine's worst-case inflow when the grid
    # outgrows the defaults (the T=64 hier presets), like
    # benchmarks.common.engine_cfg; smaller grids keep the defaults
    rangeq, burst = _EngineConfig(**cfg_kw).min_caps(tiles)
    cfg_kw["cap_rangeq"] = max(_EngineConfig.cap_rangeq,
                               1 << (rangeq - 1).bit_length())
    cfg_kw["cap_updq"] = max(_EngineConfig.cap_updq,
                             1 << (burst - 1).bit_length())
    EngineConfig = functools.partial(_EngineConfig, **cfg_kw)

    n, src, dst, val = rmat_edges(scale, edge_factor=ef, seed=1)
    g = CSRGraph.from_edges(n, src, dst, val)
    gs = alg.symmetrize(g)
    root = int(np.argmax(g.ptr[1:] - g.ptr[:-1]))
    print(f"V={g.num_vertices} E={g.num_edges} tiles={tiles} "
          f"backend={backend} noc={noc} ndies={ndies[0]}x{ndies[1]} "
          f"placement={placement}")
    print(f"{'app':10s} {'mode':6s} {'rounds':>7s} {'msgs':>9s} "
          f"{'spills':>7s} {'edges':>9s}  check")

    for mode in ("async", "bsp"):
        c = EngineConfig(mode=mode)
        pg = alg.prepare(g, tiles, scheme=placement, dies=dies)
        pgs = alg.prepare(gs, tiles, scheme=placement, dies=dies)
        for app in ("bfs", "sssp", "wcc", "pagerank", "spmv"):
            if app == "bfs":
                res = alg.bfs(pg, root, c)
                ok = (res.values == ref.bfs_ref(g, root)).all()
            elif app == "sssp":
                res = alg.sssp(pg, root, c)
                e = ref.sssp_ref(g, root)
                f = np.isfinite(e)
                ok = np.allclose(res.values[f], e[f], rtol=1e-5)
            elif app == "wcc":
                res = alg.wcc(pgs, c)
                ok = (res.values == ref.wcc_ref(gs)).all()
            elif app == "pagerank":  # keeps its barrier, as in the paper
                prc = EngineConfig(mode="bsp")
                if prc.adapt:
                    # adaptive preset: migrate at epoch boundaries from
                    # the recorder's busy cycles (repro.place); the
                    # relabeling contract keeps the reference check intact
                    import dataclasses as _dc

                    from repro.place import adaptive_pagerank
                    prc = _dc.replace(prc, trace=True, trace_rounds=4096)
                    res, _, plans = adaptive_pagerank(g, pg, iters=8,
                                                      cfg=prc)
                    assert plans, "adapt preset applied no migration plan"
                else:
                    res = alg.pagerank(pg, iters=8, cfg=prc)
                ok = np.allclose(res.values, ref.pagerank_ref(g, iters=8),
                                 rtol=2e-3, atol=1e-7)
            else:
                x = np.random.default_rng(0).normal(
                    size=g.num_vertices).astype(np.float32)
                res = alg.spmv(pg, x, c)
                ok = np.allclose(res.values, ref.spmv_ref(g, x), rtol=2e-4,
                                 atol=1e-4)
            s = res.stats
            print(f"{app:10s} {mode:6s} {int(s.rounds):7d} "
                  f"{int(s.msgs_range + s.msgs_update):9d} "
                  f"{int(s.spills_range + s.spills_update):7d} "
                  f"{int(s.edges_scanned):9d}  "
                  f"{'OK' if ok else 'FAIL'}")
            assert ok, app
            assert int(s.drops) == 0

    # Flight recorder (--trace): the async BFS again with the per-round
    # trace on — results stay bit-identical (asserted), and the run's
    # timeline lands in a Chrome/Perfetto JSON (ui.perfetto.dev) plus the
    # utilization / work-imbalance / queue-depth table of repro.trace.
    if args.trace:
        import dataclasses
        from repro.trace import (format_summary, reconcile_cycles,
                                 summarize, write_perfetto)
        pg_t = alg.prepare(g, tiles, scheme=placement, dies=dies)
        cfg0 = EngineConfig(mode="async")
        cfg_t = dataclasses.replace(cfg0, trace=True, trace_rounds=4096)
        base = alg.bfs(pg_t, root, cfg0)
        res = alg.bfs(pg_t, root, cfg_t)
        assert (res.values == base.values).all() \
            and float(res.stats.cycles) == float(base.stats.cycles), \
            "the flight recorder must not perturb the run"
        rec = reconcile_cycles(res.trace,
                               float(np.asarray(res.stats.cycles)))
        doc = write_perfetto(res.trace, args.trace,
                             meta={"app": "bfs", "noc": noc,
                                   "placement": placement,
                                   "tiles": tiles, "scale": scale})
        print(f"\nflight recorder: bfs traced "
              f"{int(res.stats.rounds)} rounds -> {args.trace} "
              f"({len(doc['traceEvents'])} events), cycle reconcile "
              f"exact={rec['exact']}")
        print(format_summary(summarize(res.trace)))

    # NoC topology ablation: same BFS, five fabrics (the hier rows run the
    # multi-die composition with and without die-local placement — the
    # die-crossing fraction is the new hierarchy column).  Uncapped links
    # expose each wiring's hotspot structure; drops stay 0 by construction.
    from repro.noc import grid_shape
    from repro.perf import die_crossing_frac
    rows_, cols_ = grid_shape(tiles)
    hnd = ndies if ndies != (1, 1) else (2, 2)
    print(f"\n{'noc':22s} {'rounds':>7s} {'spills':>7s} "
          f"{'max_link_occ':>13s} {'avg_hops':>9s} {'die_frac':>9s}")
    pg = alg.prepare(g, tiles)
    expect = ref.bfs_ref(g, root)
    fabrics = [("ideal", pg), ("mesh", pg), ("torus", pg), ("ruche", pg)]
    if rows_ % hnd[0] or cols_ % hnd[1]:
        # a single-die "hier" row would just be the mesh again — say so
        # instead of printing misleadingly-labeled rows
        print(f"(hier rows skipped: {rows_}x{cols_} grid not divisible "
              f"into {hnd[0]}x{hnd[1]} dies)")
    else:
        pg_dl = alg.prepare(g, tiles, scheme="low_order_dielocal", dies=hnd)
        fabrics += [("hier", pg), ("hier+dielocal", pg_dl)]
    for name, pgx in fabrics:
        noc_kind = name.split("+")[0]
        res = alg.bfs(pgx, root, EngineConfig(
            noc=noc_kind, ndies_y=hnd[0] if noc_kind == "hier" else 1,
            ndies_x=hnd[1] if noc_kind == "hier" else 1))
        s = res.stats
        hist = np.asarray(s.hop_histogram)
        avg = (hist * np.arange(len(hist))).sum() / max(hist.sum(), 1)
        die_frac = die_crossing_frac(s)
        assert (res.values == expect).all() and int(s.drops) == 0
        print(f"{name:22s} {int(s.rounds):7d} "
              f"{int(s.spills_range + s.spills_update):7d} "
              f"{int(s.max_link_occupancy):13d} {avg:9.2f} {die_frac:9.2f}")

    # Query serving: N BFS/SSSP sources batched through the engine as
    # vmapped query lanes (src/repro/serve/) — one resident graph, shared
    # rounds, per-query results identical to solo runs.  The queries/sec
    # line is the serving headline of benchmarks/fig12_serving.py.
    if args.queries > 0:
        from repro.serve import Frontend
        deg = np.asarray(g.ptr[1:] - g.ptr[:-1])
        rng = np.random.default_rng(0)
        srcs = rng.choice(np.flatnonzero(deg > 0), size=args.queries)
        width = min(args.queries, 16)
        print(f"\nserving {args.queries} queries, {width} lanes "
              f"(static batches, burst arrivals)")
        print(f"{'app':10s} {'rounds':>7s} {'seq_rounds':>10s} "
              f"{'qps':>12s} {'pJ/query':>10s} {'lat_p95':>8s}  check")
        for app, rf in (("bfs", ref.bfs_ref), ("sssp", ref.sssp_ref)):
            fe = Frontend(pg, app=app, cfg=EngineConfig(), width=width)
            rep = fe.serve(srcs)
            ok = rep.drops == 0
            for rec in rep.records:
                e = rf(g, rec.source)
                f = np.isfinite(e)
                ok = ok and bool(np.allclose(rec.values[f], e[f],
                                             rtol=1e-5)) \
                    and bool(np.isinf(rec.values[~f]).all())
            if args.queries > 1:  # batching must amortize rounds
                ok = ok and rep.total_rounds < rep.seq_rounds
            print(f"{app:10s} {rep.total_rounds:7d} {rep.seq_rounds:10d} "
                  f"{rep.qps:12.1f} {rep.j_per_query * 1e12:10.1f} "
                  f"{rep.latency_cycles(95):8.0f}  "
                  f"{'OK' if ok else 'FAIL'}")
            assert ok, app

    # Task-graph workloads on the generic executor: a different T3 fold
    # (k-core peel) and a 4-channel chain (2-hop triangle counting).
    print(f"\n{'app':10s} {'rounds':>7s} {'msgs':>9s} {'result':>10s}  check")
    for k in (2, 3):
        res = alg.kcore(pgs, k, EngineConfig())
        ok = (res.values == ref.kcore_ref(gs, k)).all()
        s = res.stats
        print(f"{'kcore' + str(k):10s} {int(s.rounds):7d} "
              f"{int(np.asarray(s.msgs).sum()):9d} "
              f"{int(res.values.sum()):10d}  {'OK' if ok else 'FAIL'}")
        assert ok and int(s.drops) == 0
    pgt = alg.prepare_triangles(gs, tiles)
    # triangles pins its edge shard to vmem (the closing fold
    # binary-searches the resident adjacency) — honor the pin here
    # instead of asking resolve_edge_space for the impossible.
    res = alg.triangles(pgt, EngineConfig(edge_space="vmem"))
    ok = (res.values == ref.triangles_ref(gs, key=pgt.place)).all()
    s = res.stats
    print(f"{'triangles':10s} {int(s.rounds):7d} "
          f"{int(np.asarray(s.msgs).sum()):9d} "
          f"{int(res.values.sum()):10d}  {'OK' if ok else 'FAIL'}")
    assert ok and int(s.drops) == 0
    print("per-channel msgs (range/wedge/range2/close):",
          np.asarray(s.msgs).tolist())


if __name__ == "__main__":
    main()
