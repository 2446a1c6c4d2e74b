"""Plain references for the benchmark's comparisons, and their controls.

``bfs_ref`` and ``pagerank_ref`` are the benchmark's own copies of the
sequential numpy oracles (level-synchronous BFS; power iteration with
dangling-mass redistribution in float64), kept here so that the
yardstick does not move with the program.  The controls break one
guarantee each, as a tempting shortcut would, and must fail the
comparison (``bench/tests/test_control.py``):

* ``bfs_lossy``          loses a share of the relaxation messages
                         (the configuration states that none is dropped);
* ``pagerank_bf16``      holds ranks and contributions in bfloat16, the
                         precision below the float32 the engine states.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np


def _segments(g, frontier: np.ndarray) -> np.ndarray:
    """Edge indices of every frontier vertex's segment, concatenated."""
    deg = np.diff(g.ptr)
    cnt = deg[frontier]
    first = np.repeat(g.ptr[frontier] - (np.cumsum(cnt) - cnt), cnt)
    return first + np.arange(int(cnt.sum()), dtype=np.int64)


def bfs_ref(g, root: int, lose: float = 0.0, rng=None) -> np.ndarray:
    """Hop counts from ``root``; unreachable = +inf.  ``lose`` > 0 drops
    that share of the messages (the control)."""
    dist = np.full(g.num_vertices, np.inf, np.float64)
    dist[root] = 0
    frontier = np.array([root], np.int64)
    d = 0
    while frontier.size:
        nb = g.dst[_segments(g, frontier)]
        if lose:
            nb = nb[rng.random(nb.size) >= lose]
        reached = np.zeros(g.num_vertices, bool)
        reached[nb[dist[nb] == np.inf]] = True
        frontier = np.flatnonzero(reached)
        d += 1
        dist[frontier] = d
    return dist


def bfs_lossy(g, root: int, rng, lose: float = 1e-2) -> np.ndarray:
    return bfs_ref(g, root, lose, rng)


def pagerank_ref(g, damping: float = 0.85, iters: int = 20,
                 dtype=np.float64) -> np.ndarray:
    """Power iteration with dangling-mass redistribution.  Sums run in
    float64; ``dtype`` is the precision ranks and contributions are held
    in between them (float64, or bfloat16 for the control)."""
    n = g.num_vertices
    deg = np.diff(g.ptr)
    src = np.repeat(np.arange(n), deg)
    rank = np.full(n, 1.0 / n).astype(dtype)
    for _ in range(iters):
        r = rank.astype(np.float64)
        contrib = np.where(deg > 0, r / np.maximum(deg, 1), 0.0)
        contrib = contrib.astype(dtype).astype(np.float64)
        acc = np.bincount(g.dst, weights=contrib[src], minlength=n)
        dangling = r[deg == 0].sum()
        rank = ((1 - damping) / n
                + damping * (acc + dangling / n)).astype(dtype)
    return rank.astype(np.float64)


def pagerank_bf16(g, damping: float = 0.85, iters: int = 20) -> np.ndarray:
    return pagerank_ref(g, damping, iters, ml_dtypes.bfloat16)
