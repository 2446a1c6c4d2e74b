"""Device time per round leg, and the program's host spans, of one
cell's traced engine call.

    python3 bench/legs.py --workload <name> --seed <n>

The engine runs each leg of its round in a ``jax.named_scope``
(``repro.core.engine.ROUND_LEGS``), and ``engine_leg_map`` names the leg
of every operation of the compiled engine; the drivers emit
``HOST_SPANS``.  This script builds the cell as ``bench/run.py`` does,
profiles the same engine call its ``--trace 1`` run profiles
(``run.Profiler``: the window's second call and the host work before
it), and prints one JSON object: device ms per engine round in each leg,
the operations of the top ten with their legs, the idle gaps named by
the program's spans, and the durations of those spans.

The reductions (``leg_seconds``, ``span_seconds``) work on the
``bench/trace.py`` ``Trace`` the harness loads, so that the harness can
report them as per-layer metrics.  It refuses any platform but a TPU,
like ``bench/run.py``; the tests drive ``profile_cell`` on the CPU.

The compilation cache is keyed on the programs' metadata too: a program
that differs from a cached one only in its named scopes (its parent's,
say) would otherwise load that executable, whose metadata names no leg.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                if p not in sys.path]

from bench import trace as tr  # noqa: E402

# the benchmark's copies of repro.core.engine.ROUND_LEGS and
# repro.core.algorithms.HOST_SPANS (a test ties them)
LEGS = ("control", "source", "queue", "route", "link_count", "scan", "fold",
        "telemetry")
HOST_SPANS = ("engine_call", "engine_dispatch", "engine_wait", "init_state",
              "epoch_update", "to_original")


def share_ns(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Each interval's share of the union of all: every instant goes to
    the interval that started last among those covering it (the longer
    first where two start together).  Nested intervals, as on a TPU's one
    line of operations, get their own time (``trace.self_ns``); where the
    CPU's threads run operations side by side the overlap is split, so
    the shares always sum to the union's length."""
    order = np.lexsort((-end, start)).tolist()
    start, end = start.tolist(), end.tolist()
    own = [0] * len(start)
    stack, now = [], None

    def advance(until):
        nonlocal now
        while stack:
            top = stack[-1]
            stop = end[top] if until is None else min(end[top], until)
            if stop > now:
                own[top] += stop - now
                now = stop
            if end[top] > now:
                return
            stack.pop()

    for i in order:
        if now is not None:
            advance(start[i])
        now = start[i] if now is None else max(now, start[i])
        stack.append(i)
    if stack:
        advance(None)
    return np.array(own, np.int64)


def _window(t: tr.Trace, window: str):
    wins = [(s, e) for n, s, e in t.spans if n == window]
    if not wins:
        raise ValueError(f"the trace has no {window!r} span")
    return wins[0]


def leg_seconds(t: tr.Trace, legs: dict, window: str = "window") -> dict:
    """{leg: s} of the first device's busy time in the ``window`` span,
    each operation's share (``share_ns``) under its leg in ``legs``
    ({operation name: leg}); "unmapped" holds the operations ``legs``
    lacks.  The legs sum to ``trace.reduce``'s ``first_busy_s``."""
    lo, hi = _window(t, window)
    ops = t.devices[min(t.devices)]
    s, e, kept = tr.clip(ops.start, ops.end, lo, hi)
    names = [n for n, k in zip(ops.name, kept) if k]
    out = {}
    for n, dt in zip(names, share_ns(s, e).tolist()):
        leg = legs.get(n, "unmapped")
        out[leg] = out.get(leg, 0) + dt
    return {leg: dt / 1e9 for leg, dt in out.items()}


def span_seconds(t: tr.Trace, name: str, window: str = "window") -> list:
    """Durations (s) of the ``name`` spans inside the ``window`` span."""
    lo, hi = _window(t, window)
    return [(e - s) / 1e9 for n, s, e in t.spans
            if n == name and lo <= s and e <= hi]


def profile_cell(cell: dict, config: dict, traffic_mix: dict, seed: int,
                 devices) -> dict:
    """Build the cell, profile the engine call ``bench/run.py``'s traced
    run profiles, and reduce it by leg and span."""
    from bench import graph500, run, traffic
    from repro.core import algorithms as alg

    used = devices[:cell["chips"]]
    g = graph500.build(config)
    pg = alg.prepare(g, T=config["tiles"])
    cfg = run.engine_config(config)
    mesh = run.make_mesh(config, used)
    loop = traffic.Loop(traffic_mix, g, pg, cfg, mesh, seed)
    loop.warmup()
    logdir = tempfile.mkdtemp(prefix="bench-legs-")
    try:
        with run.Profiler(alg, logdir) as profile:
            units = []
            while profile.calls < 2:
                units.append(loop.unit(len(units), time.perf_counter))
        if not profile.taken or any(u.error for u in units):
            raise RuntimeError(f"no whole engine call was profiled: "
                               f"{[u.error for u in units]}")
        t0 = time.perf_counter()
        program = {"bfs": alg.BFS, "pagerank": alg.PAGERANK}[loop.kind]
        legs = alg.engine_leg_map(pg, program, cfg, mesh)
        leg_map_s = time.perf_counter() - t0
        t = tr.load(logdir, run.SPANS + HOST_SPANS)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    r = tr.reduce(t)
    leg_s = leg_seconds(t, legs)
    rounds = profile.rounds
    busy = r["first_busy_s"]
    return {
        "workload": cell["name"], "rounds": rounds, "busy_s": r["busy_s"],
        "window_s": r["window_s"], "round_ms": 1e3 * r["busy_s"] / rounds,
        "leg_ms": {leg: 1e3 * leg_s.get(leg, 0.0) / rounds
                   for leg in sorted(leg_s, key=lambda k: -leg_s[k])},
        "leg_share": {leg: s / busy for leg, s in leg_s.items()},
        "stray_share": (leg_s.get("unscoped", 0.0)
                        + leg_s.get("unmapped", 0.0)) / busy,
        "device_ops": [[n, s, legs.get(n, "unmapped")]
                       for n, s in r["device_ops"]],
        "idle_gaps": r["idle_gaps"],
        "host_span_ms": {n: [1e3 * s for s in span_seconds(t, n)]
                         for n in HOST_SPANS},
        "leg_map_s": leg_map_s,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    from bench import run

    import jax

    bench = run.load_benchmark()
    cell, config, traffic_mix = run.resolve(bench, args.workload)
    devices = run.require_devices(cell["chips"])
    run.use_compile_cache()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    out = profile_cell(cell, config, traffic_mix, args.seed, devices)
    dev = devices[0]
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
