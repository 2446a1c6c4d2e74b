"""Run a cell's control through the comparison that decides ``correct``.

    python3 bench/control.py --workload <name> --seeds <n> [<n> ...]

For each seed this builds the cell's graph as a run does, answers the
inputs a run of that seed issues with the control of
``bench/reference.py`` in the program's place (every one of the seed's
search keys, or one PageRank call), and hands those answers to
``bench/check.py`` as the units of a run.  It prints one JSON line per
seed: ``correct``, and each number compared beside its limit.  A sound
control comes out not correct.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, graph500, reference, run  # noqa: E402
from bench.traffic import Unit  # noqa: E402


def control_units(traffic: dict, g, seed: int) -> list:
    """The units a run of ``seed`` issues, answered by the control."""
    if traffic["algorithm"] == "bfs":
        lossy = graph500.seed_rng(seed, graph500.CONTROL_STREAM)
        return [Unit(int(r), 0.0, 0.0, epochs=1,
                     values=reference.bfs_lossy(g, int(r), lossy))
                for r in graph500.search_keys(g, int(traffic["search_keys"]),
                                              seed)]
    iters = int(traffic["iters"])
    return [Unit(None, 0.0, 0.0, epochs=iters, values=reference.pagerank_bf16(
        g, float(traffic["damping"]), iters))]


def control_run(config: dict, traffic: dict, seed: int) -> dict:
    g = graph500.build(config)
    units = control_units(traffic, g, seed)
    checks, failed = check.compare(traffic["algorithm"], traffic, g, units)
    return {"correct": check.verdict(checks, failed),
            "attempted": len(units), "failed": failed, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    _, config, traffic = run.resolve(run.load_benchmark(), args.workload)
    for seed in args.seeds:
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              **control_run(config, traffic, seed))),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
