"""Smoke test of the graph engine's main path on a TPU.

One process, through the entry points a user calls
(``repro.core.algorithms`` and ``repro.serve``), over one resident
Graph500 Kronecker graph (``rmat_edges(scale, edge_factor=16)``,
symmetrized, partitioned with ``alg.prepare(g, T)``, edge shards declared
in HBM):

* ``bfs``       BFS from 4 seeded roots, exact against ``bfs_ref``;
* ``pagerank``  PageRank with ``iters=2`` (after a one-epoch call that
                compiles), allclose to ``pagerank_ref``;
* ``serve``     16 seeded BFS sources through ``repro.serve.Frontend`` at
                width 8, every lane exact against ``bfs_ref``.

``--chips 4`` runs only the SPMD path and its twin, at scale 21 unless
``--scale`` says otherwise: BFS under ``spmd_engine_call`` over a
4-device mesh (one tile per chip, so T=4) against ``LocalComm`` with T=4
on device 0, bitwise equal in values and ``Stats``.

Each one-chip phase prints, on its own line, host set-up seconds,
first-call (compile + run) seconds, warm wall seconds
(``block_until_ready``), rounds, edges scanned and the device's
``peak_bytes_in_use``; the SPMD phase runs each path once.  The script
exits non-zero when JAX finds no TPU, and when any phase fails; the last
line of a passing run is one JSON object naming the device.

    python chip_smoke.py [--scale 17] [--tiles 16] [--seed 0] [--chips 1]

The default scale is what fits the smoke's 1200 s on one v5e with room
to spare: serving's sixteen traversals bound it.  Scale 22 with T=16 and
HBM edge shards compiles for a v5e and fits its memory
(``tests/test_chip_compile.py``), but its phases do not fit the 1200 s
yet (PERF.md).
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import algorithms as alg  # noqa: E402
from repro.core import reference as ref  # noqa: E402
from repro.core.engine import BFS, EngineConfig  # noqa: E402
from repro.core.graph import CSRGraph, rmat_edges  # noqa: E402
from repro.core.program import as_program, sized_cfg  # noqa: E402
from repro.serve import Frontend  # noqa: E402

EDGE_FACTOR = 16      # Graph500
BFS_ROOTS = 4
SERVE_QUERIES = 16
SERVE_WIDTH = 8
PR_ITERS = 2
SCALE = 17            # fits 1200 s on one chip (module docstring)
SPMD_SCALE = 21       # the largest a T=4 tile's declarations admit
# Per-round budgets (EngineConfig's defaults otherwise).  A larger update
# replay budget and fabric let a tile drain what its edge scans emit: a
# scale-18 BFS at T=16 takes 4555 rounds of 3.66 ms with these against
# 11650 rounds of 2.70 ms with the defaults (PERF.md).  A T=4 tile holds
# four times the graph, and the range budgets grow with it.
BUDGETS = dict(f_pop=64, r_pop=64, u_pop=2048, cap_route_update=256)
SPMD_BUDGETS = dict(f_pop=128, r_pop=128, u_pop=1024, cap_route_range=64,
                    cap_route_update=512)


def log(**kv):
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def require_tpu(chips: int):
    """The device list, or exit non-zero: this smoke never falls back to
    another platform."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX's platform is "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, JAX sees {len(devs)}")
    return devs


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def engine_config(T: int, budgets: dict = BUDGETS) -> EngineConfig:
    """The smoke's engine configuration for ``T`` tiles: the edge shard
    declared in HBM (a scale-22 shard is 64 MiB per tile at T=16, past
    the per-tile VMEM budget), per-round ``budgets`` (``BUDGETS``, or
    ``SPMD_BUDGETS`` for the four-chip phase), and queues sized by the
    engine (``sized_cfg``).  Budgets only set how much work
    a tile takes per round; the TSU's credit stop keeps any of them from
    dropping, and the smoke still checks ``drops == 0``."""
    return sized_cfg(EngineConfig(edge_space="hbm", **budgets),
                     as_program(BFS), T)


def build_graph(scale: int, seed: int) -> CSRGraph:
    """The Graph500 Kronecker graph, symmetrized and unweighted, as BFS
    and PageRank read it: every generated edge in both directions,
    deduplicated (the edge set ``alg.symmetrize`` gives, built in one
    pass)."""
    n, src, dst, _ = rmat_edges(scale, edge_factor=EDGE_FACTOR, seed=seed)
    return CSRGraph.from_edges(n, np.concatenate([src, dst]),
                               np.concatenate([dst, src]))


def pick_vertices(g: CSRGraph, k: int, rng) -> list:
    """``k`` distinct vertices with at least one edge (Graph500 roots)."""
    deg = g.ptr[1:] - g.ptr[:-1]
    return [int(v) for v in rng.choice(np.flatnonzero(deg > 0), k,
                                       replace=False)]


def timed(fn, *args, **kw):
    """(result, wall seconds) with every device array of the result
    ready before the clock stops."""
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    jax.block_until_ready(vars(out))
    return out, time.perf_counter() - t0


def _check_bfs(res, expect, what):
    np.testing.assert_array_equal(res.values, expect, err_msg=what)
    assert int(res.stats.drops) == 0, f"{what}: dropped messages"


def phase_bfs(g, pg, cfg, roots) -> dict:
    t0 = time.perf_counter()
    expect = [ref.bfs_ref(g, r) for r in roots]
    ref_s = time.perf_counter() - t0
    walls, rounds, edges = [], [], []
    for r, want in zip(roots, expect):
        res, dt = timed(alg.bfs, pg, r, cfg)
        _check_bfs(res, want, f"bfs root {r}")
        walls.append(dt)
        rounds.append(int(res.stats.rounds))
        edges.append(int(res.stats.edges_scanned))
    # the first root's call compiles; the other roots are warm
    return dict(phase="bfs", roots=len(roots), ref_s=ref_s,
                first_call_s=walls[0], warm_s=walls[1:], rounds=rounds,
                edges_scanned=edges, peak_bytes=peak_bytes())


def phase_pagerank(g, pg, cfg, iters: int = PR_ITERS) -> dict:
    """A one-epoch call compiles the epoch program; the ``iters``-epoch
    call runs warm.  Both are checked."""
    t0 = time.perf_counter()
    expect = {k: ref.pagerank_ref(g, iters=k) for k in (1, iters)}
    ref_s = time.perf_counter() - t0
    # f32 rank sums against the f64 oracle; ranks are ~1/V, so the
    # absolute floor scales with V
    tol = dict(rtol=2e-3, atol=1e-3 / g.num_vertices)
    res, first_s = timed(alg.pagerank, pg, iters=1, cfg=cfg)
    np.testing.assert_allclose(res.values, expect[1], **tol)
    res, warm_s = timed(alg.pagerank, pg, iters=iters, cfg=cfg)
    np.testing.assert_allclose(res.values, expect[iters], **tol)
    assert res.epochs == iters and int(res.stats.drops) == 0
    return dict(phase="pagerank", iters=iters, ref_s=ref_s,
                first_call_s=first_s, warm_s=warm_s,
                rounds=int(res.stats.rounds),
                edges_scanned=int(res.stats.edges_scanned),
                peak_bytes=peak_bytes())


def phase_serve(g, pg, cfg, sources, width: int = SERVE_WIDTH) -> dict:
    """Two batches through one Frontend: the first compiles, the second
    is warm."""
    t0 = time.perf_counter()
    expect = {s: ref.bfs_ref(g, s) for s in sources}
    ref_s = time.perf_counter() - t0
    fe = Frontend(pg, app="bfs", cfg=cfg, width=width)
    reports = []
    for batch in (sources[:width], sources[width:]):
        rep, dt = timed(fe.serve, batch)
        assert rep.queries == len(batch) and rep.drops == 0
        for rec in rep.records:
            np.testing.assert_array_equal(
                rec.values, expect[rec.source],
                err_msg=f"served bfs source {rec.source}")
        reports.append((rep, dt))
    (_, first_s), (warm, warm_s) = reports
    return dict(phase="serve", queries=len(sources), width=width,
                ref_s=ref_s, first_call_s=first_s, warm_s=warm_s,
                rounds=warm.total_rounds, edges_scanned=warm.edges_total,
                peak_bytes=peak_bytes())


def phase_spmd(g, pg, cfg, root: int, mesh) -> dict:
    """BFS as one tile per device under shard_map, against the LocalComm
    twin of the same partition on the default device: values and every
    Stats field bitwise equal.  Each path runs once (its first call, so
    compile included): the four-chip machine is the costly one."""
    t0 = time.perf_counter()
    expect = ref.bfs_ref(g, root)
    ref_s = time.perf_counter() - t0
    out = dict(phase="spmd", devices=mesh.size, T=pg.T, ref_s=ref_s)
    runs = {}
    runs["spmd"], out["spmd_first_call_s"] = timed(alg.bfs, pg, root, cfg,
                                                   mesh=mesh)
    runs["local"], out["local_first_call_s"] = timed(alg.bfs, pg, root, cfg)
    for name, res in runs.items():
        _check_bfs(res, expect, f"{name} bfs root {root}")
    for field, a, b in zip(runs["spmd"].stats._fields, runs["spmd"].stats,
                           runs["local"].stats):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"Stats.{field}: spmd {np.asarray(a)} != local {np.asarray(b)}")
    out.update(rounds=int(runs["spmd"].stats.rounds),
               edges_scanned=int(runs["spmd"].stats.edges_scanned),
               peak_bytes=peak_bytes())
    return out


def run_phases(g, pg, cfg, seed: int):
    """The one-chip phases, in order; each is logged as it ends."""
    rng = np.random.default_rng(seed)
    roots = pick_vertices(g, BFS_ROOTS, rng)
    sources = pick_vertices(g, SERVE_QUERIES, rng)
    log(**phase_bfs(g, pg, cfg, roots))
    log(**phase_pagerank(g, pg, cfg))
    log(**phase_serve(g, pg, cfg, sources))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=None,
                    help=f"Graph500 scale (default {SCALE}, or "
                         f"{SPMD_SCALE} with --chips 4)")
    ap.add_argument("--tiles", type=int, default=16,
                    help="emulated Dalorex tiles T (one chip)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    dev = devs[0]
    log(platform=dev.platform, kind=repr(dev.device_kind), count=len(devs),
        jax=jax.__version__, libtpu=importlib.metadata.version("libtpu"),
        compile_cache=enable_compile_cache())

    spmd = args.chips == 4
    scale = args.scale or (SPMD_SCALE if spmd else SCALE)
    T = 4 if spmd else args.tiles
    t0 = time.perf_counter()
    g = build_graph(scale, args.seed)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    pg = alg.prepare(g, T=T)
    cfg = engine_config(T, SPMD_BUDGETS if spmd else BUDGETS)
    part_s = time.perf_counter() - t0
    log(phase="graph", scale=scale, edge_factor=EDGE_FACTOR,
        V=g.num_vertices, E=g.num_edges, T=T, e_chunk=pg.e_chunk,
        v_chunk=pg.v_chunk, edge_space=cfg.edge_space, gen_s=gen_s,
        partition_s=part_s)

    if spmd:
        mesh = jax.make_mesh((4,), ("x",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        root = pick_vertices(g, 1, np.random.default_rng(args.seed))[0]
        log(**phase_spmd(g, pg, cfg, root, mesh))
    else:
        run_phases(g, pg, cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
