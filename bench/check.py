"""The comparison that decides ``correct``, run once the window has closed.

Every unit the window issued is compared with the benchmark's own
reference (``bench/reference.py``) over the same graph:

* ``bfs``: each traversal's levels, vertex by vertex, against
  ``bfs_ref`` from the same root; the number compared is the most
  vertices any traversal got wrong, with the limit 0;
* ``pagerank``: each call's ranks against the float64 ``pagerank_ref``;
  the number compared is the largest relative error of any vertex in any
  call, with the limit the traffic file states (``limits.rank_rel_err``);

and, for both, the messages the engine dropped (limit 0).  A unit that
raised, or that breaks a limit, is a failed unit.  The same pass fills
each unit's ``work``: the traversed edges of its search (Graph500's
count, from the graph and the reference's levels) or the directed edges
its epochs pushed.
"""
from __future__ import annotations

import numpy as np

from bench import graph500, reference


def compare(kind: str, traffic: dict, g, units) -> tuple[dict, int]:
    """(checks, failed): ``checks`` maps each number compared to
    ``{"value", "limit"}``; ``failed`` counts the failed units."""
    limits = traffic["limits"]
    bad = [u.error is not None or u.drops > 0 for u in units]
    if kind == "bfs":
        worst = 0
        for i, u in enumerate(units):
            want = reference.bfs_ref(g, u.arg)
            u.work = graph500.component_edges(g, want)
            if u.values is not None:
                wrong = int(np.count_nonzero(u.values != want))
                worst = max(worst, wrong)
                bad[i] |= wrong > limits["level_mismatches"]
        checks = {"level_mismatches": worst}
    elif kind == "pagerank":
        want = reference.pagerank_ref(g, float(traffic["damping"]),
                                      int(traffic["iters"]))
        worst = 0.0
        for i, u in enumerate(units):
            u.work = g.num_edges * u.epochs
            if u.values is not None:
                err = float(np.max(np.abs(u.values - want) / want))
                err = err if np.isfinite(err) else float("inf")
                worst = max(worst, err)
                bad[i] |= not err <= limits["rank_rel_err"]
        checks = {"rank_rel_err": worst}
    else:
        raise ValueError(f"unknown algorithm {kind!r}")
    checks["drops"] = sum(u.drops for u in units)
    return ({k: {"value": v, "limit": limits[k]} for k, v in checks.items()},
            sum(bad))


def verdict(checks: dict, failed: int) -> bool:
    """``correct``: no unit failed, and every number is within its limit."""
    return failed == 0 and all(c["value"] <= c["limit"]
                               for c in checks.values())
