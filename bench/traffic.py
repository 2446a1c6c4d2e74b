"""The one traffic generator: a closed loop of engine calls.

A traffic mix is a data file ``bench/traffic/<name>.json`` that names the
algorithm the loop drives (the host driver of ``repro.core.algorithms``
of that name), its arguments, how the seed picks each call's input, and
the limits of the comparison that decides ``correct``.  One unit of
traffic is one call of that host driver; the loop issues the next as soon as
the previous one has returned, until the window has passed.

Algorithms and what a unit is:

* ``bfs``       one traversal ``alg.bfs(pg, root, cfg, mesh)``; the
                roots are ``search_keys`` distinct vertices of nonzero
                degree drawn from ``--seed`` (Graph500's search keys),
                traversed in the order drawn, and again from the first
                if the window outlasts them; the warm-up traverses from
                a vertex of degree 0;
* ``pagerank``  one call ``alg.pagerank(pg, damping, iters, cfg=cfg,
                mesh=mesh)`` from the uniform start; the warm-up runs it
                over a copy of the partition with every degree zeroed,
                so that the same programs run on an empty frontier.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import graph500


@dataclasses.dataclass
class Unit:
    """One call in the window, with what the checks and metrics read."""

    arg: object           # the root (bfs) or None
    start: float          # host clock, s
    end: float
    values: np.ndarray | None = None
    rounds: int = 0
    edges_scanned: int = 0
    drops: int = 0
    epochs: int = 0
    work: int = 0         # edges the unit's result counts (bench/check.py)
    error: str | None = None


class Loop:
    """Inputs and calls of one traffic mix over one graph."""

    def __init__(self, traffic: dict, g, pg, cfg, mesh, seed: int):
        from repro.core import algorithms as alg

        self.alg, self.traffic = alg, traffic
        self.kind = traffic["algorithm"]
        self.g, self.pg, self.cfg, self.mesh = g, pg, cfg, mesh
        deg = graph500.degrees(g)
        if self.kind == "bfs":
            self.args = [int(v) for v in graph500.search_keys(
                g, int(traffic["search_keys"]), seed)]
            self.warm_arg = int(np.flatnonzero(deg == 0)[0])
        elif self.kind == "pagerank":
            self.args = [None]
        else:
            raise ValueError(f"unknown algorithm {self.kind!r}")

    def call(self, arg, pg=None):
        pg = self.pg if pg is None else pg
        if self.kind == "bfs":
            return self.alg.bfs(pg, arg, self.cfg, mesh=self.mesh)
        return self.alg.pagerank(pg, damping=float(self.traffic["damping"]),
                                 iters=int(self.traffic["iters"]),
                                 cfg=self.cfg, mesh=self.mesh)

    def warmup(self):
        """One call on the cell's own shapes with next to no work."""
        import jax

        if self.kind == "bfs":
            res = self.call(self.warm_arg)
        else:
            empty = dataclasses.replace(self.pg,
                                        deg=jax.numpy.zeros_like(self.pg.deg))
            res = self.call(None, empty)
        jax.block_until_ready(res.stats)

    def unit(self, i: int, clock) -> Unit:
        import jax

        arg = self.args[i % len(self.args)]
        u = Unit(arg, clock(), 0.0)
        try:
            res = self.call(arg)
            jax.block_until_ready(res.stats)
            u.values = res.values
            u.rounds = int(res.stats.rounds)
            u.edges_scanned = int(res.stats.edges_scanned)
            u.drops = int(res.stats.drops)
            u.epochs = int(res.epochs)
        except Exception as e:  # a failed call is a failed unit, not a crash
            u.error = f"{type(e).__name__}: {e}"
        u.end = clock()
        return u
