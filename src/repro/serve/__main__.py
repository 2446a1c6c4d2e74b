"""CLI: serve a stream of point queries against a resident preset graph.

    PYTHONPATH=src python -m repro.serve --preset rmat-small --queries 64 \
        --batch 16 --app bfs --arrival poisson --gap 5e4 --policy static

Prints the aggregate throughput/latency report (modeled cycles) and, with
``--per-query``, one line per served query.
"""
from __future__ import annotations

import argparse

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.serve",
        description="Batched query serving over a resident graph")
    ap.add_argument("--preset", default="rmat-small",
                    help="repro.configs.dalorex_graph preset")
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8, help="lane width B")
    ap.add_argument("--app", default="bfs", choices=("bfs", "sssp"))
    ap.add_argument("--arrival", default="burst",
                    choices=("burst", "uniform", "poisson"))
    ap.add_argument("--gap", type=float, default=0.0,
                    help="mean interarrival gap, modeled cycles")
    ap.add_argument("--policy", default="static",
                    choices=("static", "continuous"))
    ap.add_argument("--backend", default=None, choices=("xla", "pallas"),
                    help="engine backend override (default: preset's)")
    ap.add_argument("--noc", default=None,
                    help="NoC backend override (default: preset's)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--per-query", action="store_true")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    from repro.configs.dalorex_graph import get_workload
    from repro.core import algorithms as alg
    from repro.core.engine import EngineConfig
    from repro.core.graph import CSRGraph, rmat_edges
    from repro.serve import Frontend

    enable_compile_cache()
    wl = get_workload(args.preset)
    n, src, dst, val = rmat_edges(wl.scale, edge_factor=wl.edge_factor,
                                  seed=0)
    g = CSRGraph.from_edges(n, src, dst, val)
    pg = alg.prepare(g, T=wl.tiles, scheme=wl.placement)
    # size the channel queues from the engine's own worst-case inflow
    # bounds (mirrors benchmarks/common.engine_cfg without importing the
    # benchmarks tree from inside the package)
    base = dict(f_pop=32, r_pop=32, u_pop=64, max_t2=16,
                cap_route_range=8, cap_route_update=32,
                max_rounds=200_000, backend=args.backend or wl.backend,
                noc=args.noc or wl.noc)
    if base["noc"] == "hier":
        base["ndies_y"], base["ndies_x"] = wl.ndies
    rangeq, burst = EngineConfig(**base).min_caps(wl.tiles)
    cfg = EngineConfig(
        cap_rangeq=max(512, 1 << (rangeq - 1).bit_length()),
        cap_updq=max(8192, 1 << (burst - 1).bit_length()), **base)

    rng = np.random.default_rng(args.seed)
    deg = np.asarray(g.ptr[1:] - g.ptr[:-1])
    sources = rng.choice(np.flatnonzero(deg > 0), size=args.queries)

    fe = Frontend(pg, app=args.app, cfg=cfg, width=args.batch,
                  policy=args.policy)
    rep = fe.serve(sources, arrival=args.arrival, gap=args.gap,
                   seed=args.seed)

    print(f"# preset={args.preset} V={g.num_vertices} T={wl.tiles} "
          f"backend={cfg.backend} noc={cfg.noc}")
    print(",".join(f"{k}={v}" for k, v in rep.row().items()))
    if args.per_query:
        for r in rep.records:
            print(f"q{r.qid} src={r.source} enq={r.enqueue_cycle:.0f} "
                  f"admit={r.admit_cycle:.0f} "
                  f"done={r.complete_cycle:.0f} lat={r.latency:.0f} "
                  f"rounds={r.rounds} edges={r.edges}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
