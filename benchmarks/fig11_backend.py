"""Backend bench: XLA vs Pallas tile-grid execution of the engine round.

Beyond the paper's figures: PR4's Pallas backend re-expresses the round's
queue/scan/fold legs as per-tile kernels (``src/repro/kernels/engine/``)
and PR7 fuses each channel leg into a SINGLE ``pallas_call``
(``EngineConfig.pallas_fuse``); this bench proves three things per
workload x NoC:

* **equivalence** — values, rounds, cycles and energy are bit-identical
  between ``backend="xla"`` and both pallas variants (the ``ok`` column;
  the modeled GTEPS therefore matches by construction);
* **launch accounting** — the ``launches_per_round`` column (from
  ``Stats.launches``): one launch per channel leg fused (3/round for the
  classic program, 5/round for triangles' 4-channel chain) vs the legacy
  4+ standalone kernel dispatches per round on ``pallas-nofuse``, vs 0 on
  xla.  ``benchmarks/kern_micro.py`` prices what each saved launch costs;
* **host cost** — wall-clock per engine run and per round for every
  backend, plus ``fused_round_delta_us`` on the fused rows (the
  wall-clock/round win over the unfused pallas path).  In interpret mode
  the Pallas path pays the interpreter tax on CPU; the columns exist to
  track that overhead (and, once the TPU compiler accepts the kernels,
  the win) release over release.

The backend strings are "xla", "pallas-nofuse" (``pallas_fuse=False``:
one kernel per building block + XLA glue) and "pallas" (the fused
single-launch leg, the default).  ``nocs`` sweeps the fabric; "hier" runs
the multi-die corner (a 2-die vertical split).

Rows feed ``benchmarks/smoke.py``'s BENCH json (backend=pallas rows in CI)
and the standalone ``BENCH_FIG11.json`` artifact.
"""
from __future__ import annotations

import numpy as np

from repro.core import algorithms as alg
from repro.core import reference as ref
from benchmarks.common import (engine_cfg, perf_cols, pick_root, rmat_graph,
                               stats_row, timed)

APPS = ("bfs", "sssp", "wcc", "spmv", "pagerank", "kcore", "triangles")
BACKENDS = ("xla", "pallas-nofuse", "pallas")
NOCS = ("ideal", "mesh", "torus", "ruche", "hier")


def _runner(app, g, gs, pg, pgs, pgt, root, x):
    if app == "bfs":
        return lambda cfg: alg.bfs(pg, root, cfg)
    if app == "sssp":
        return lambda cfg: alg.sssp(pg, root, cfg)
    if app == "wcc":
        return lambda cfg: alg.wcc(pgs, cfg)
    if app == "spmv":
        return lambda cfg: alg.spmv(pg, x, cfg)
    if app == "pagerank":
        return lambda cfg: alg.pagerank(pg, iters=3, cfg=cfg)
    if app == "kcore":
        return lambda cfg: alg.kcore(pgs, 2, cfg)
    if app == "triangles":
        return lambda cfg: alg.triangles(pgt, cfg)
    raise ValueError(app)


def _reference(app, g, gs, pgt, root, x):
    if app == "bfs":
        return ref.bfs_ref(g, root)
    if app == "sssp":
        return ref.sssp_ref(g, root)
    if app == "wcc":
        return ref.wcc_ref(gs)
    if app == "spmv":
        return ref.spmv_ref(g, x)
    if app == "kcore":
        return ref.kcore_ref(gs, 2)
    if app == "triangles":
        return ref.triangles_ref(gs, key=pgt.place)
    return None  # pagerank: xla-vs-pallas equivalence is the check


def _cfg(T, noc, backend):
    """Engine config for one (noc, backend-variant) cell — "hier" runs the
    multi-die corner as a 2-die vertical split of the tile grid."""
    kw = dict(ndies_y=2) if noc == "hier" else {}
    if backend == "pallas-nofuse":
        return engine_cfg(T=T, noc=noc, backend="pallas",
                          pallas_fuse=False, **kw)
    return engine_cfg(T=T, noc=noc, backend=backend, **kw)


def run(scale: int = 8, T: int = 8, apps=APPS, nocs=("ideal",),
        backends=BACKENDS, repeat: int = 1, timing: bool = True) -> list[dict]:
    """``timing=False`` drops the machine-dependent wall-clock columns so
    the rows are deterministic — what smoke.py commits to the baseline
    (paired with ``repeat=0``: one engine run per row, no timed re-run)."""
    g = rmat_graph(scale)
    gs = alg.symmetrize(g)
    pg = alg.prepare(g, T)
    pgs = alg.prepare(gs, T)
    pgt = alg.prepare_triangles(gs, T)
    root = pick_root(g)
    x = np.linspace(0.5, 1.5, g.num_vertices).astype(np.float32)
    rows = []
    for noc in nocs:
        for app in apps:
            fn = _runner(app, g, gs, pg, pgs, pgt, root, x)
            want = _reference(app, g, gs, pgt, root, x)
            base = None
            nofuse_round_us = None
            for backend in backends:
                cfg = _cfg(T, noc, backend)
                res, wall = timed(fn, cfg, repeat=repeat)
                s = stats_row(res.stats)
                p = perf_cols(res.stats, cfg)
                ok = True
                if want is not None:
                    tol = 1e-4 if app == "spmv" else 0.0
                    ok = bool(np.allclose(res.values, want, rtol=tol,
                                          atol=tol))
                if backend == "xla":
                    base = res
                elif base is not None:
                    # the equivalence contract: every pallas variant ==
                    # xla, bit for bit (launches excluded by design)
                    ok = ok and bool(np.array_equal(res.values,
                                                    base.values)) \
                        and int(res.stats.rounds) == int(base.stats.rounds) \
                        and float(res.stats.cycles) == \
                        float(base.stats.cycles) \
                        and float(res.stats.energy_pj) == \
                        float(base.stats.energy_pj) \
                        and bool(np.array_equal(np.asarray(res.stats.msgs),
                                                np.asarray(base.stats.msgs))) \
                        and bool(np.array_equal(
                            np.asarray(res.stats.spills),
                            np.asarray(base.stats.spills)))
                row = {
                    "bench": "fig11", "app": app, "noc": noc,
                    "backend": backend, "rounds": s["rounds"],
                    "msgs": s["msgs_sum"], "spills": s["spills_sum"],
                    "edges": s["edges_scanned"], "drops": s["drops"],
                    "cycles": p["cycles"], "gteps": p["gteps"],
                    "energy_pj": p["energy_pj"],
                    "ok": ok,
                }
                if backend != "xla":
                    row["launches_per_round"] = round(
                        int(res.stats.launches) / max(s["rounds"], 1), 2)
                if timing:
                    round_us = 1e6 * wall / max(s["rounds"], 1)
                    row["wall_s"] = round(wall, 4)
                    row["round_us"] = round(round_us, 2)
                    if backend == "pallas-nofuse":
                        nofuse_round_us = round_us
                    elif backend == "pallas" and nofuse_round_us is not None:
                        # the fusion win: wall-clock/round saved vs the
                        # unfused pallas path (positive = fused faster)
                        row["fused_round_delta_us"] = round(
                            nofuse_round_us - round_us, 2)
                rows.append(row)
    return rows
