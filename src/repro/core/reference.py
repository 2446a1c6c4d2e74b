"""Sequential numpy oracles for the five paper workloads.

These are the "sequential x86 executions" the paper validates its simulator
against (Section IV-B).  Every engine test asserts bit-consistent results
(exact for BFS/WCC/SpMV path counts; allclose for SSSP/PageRank floats).
"""
from __future__ import annotations

import numpy as np

from repro.core.graph import CSRGraph


def bfs_ref(g: CSRGraph, root: int) -> np.ndarray:
    """Hop counts from root; unreachable = +inf.

    Level-synchronous: each level gathers the frontier's adjacency
    segments at once, so a Graph500 scale-22 graph takes seconds."""
    dist = np.full(g.num_vertices, np.inf, np.float64)
    dist[root] = 0
    deg = g.ptr[1:] - g.ptr[:-1]
    frontier = np.array([root], np.int64)
    d = 0
    while frontier.size:
        cnt = deg[frontier]
        # edge indices of every frontier vertex's segment, concatenated
        first = np.repeat(g.ptr[frontier] - (np.cumsum(cnt) - cnt), cnt)
        nb = g.dst[first + np.arange(int(cnt.sum()), dtype=np.int64)]
        reached = np.zeros(g.num_vertices, bool)
        reached[nb[dist[nb] == np.inf]] = True
        frontier = np.flatnonzero(reached)
        d += 1
        dist[frontier] = d
    return dist


def sssp_ref(g: CSRGraph, root: int) -> np.ndarray:
    """Bellman-Ford (handles any nonnegative weights); unreachable = +inf."""
    import heapq
    dist = np.full(g.num_vertices, np.inf, np.float64)
    dist[root] = 0.0
    pq = [(0.0, root)]
    while pq:
        d, v = heapq.heappop(pq)
        if d > dist[v]:
            continue
        for e in range(g.ptr[v], g.ptr[v + 1]):
            u = g.dst[e]
            nd = d + g.val[e]
            if nd < dist[u]:
                dist[u] = nd
                heapq.heappush(pq, (nd, u))
    return dist


def wcc_ref(g: CSRGraph) -> np.ndarray:
    """Weakly connected components: label = min original vertex id in the
    component.  Assumes ``g`` is already symmetrized (the harness does)."""
    n = g.num_vertices
    label = np.arange(n)
    # union-find with path compression
    def find(x):
        r = x
        while label[r] != r:
            r = label[r]
        while label[x] != r:
            label[x], x = r, label[x]
        return r
    for v in range(n):
        for e in range(g.ptr[v], g.ptr[v + 1]):
            a, b = find(v), find(g.dst[e])
            if a != b:
                if a < b:
                    label[b] = a
                else:
                    label[a] = b
    return np.array([find(v) for v in range(n)])


def pagerank_ref(g: CSRGraph, damping: float = 0.85, iters: int = 20
                 ) -> np.ndarray:
    """Power iteration with dangling-mass redistribution (float64)."""
    n = g.num_vertices
    deg = (g.ptr[1:] - g.ptr[:-1]).astype(np.float64)
    rank = np.full(n, 1.0 / n)
    for _ in range(iters):
        contrib = np.where(deg > 0, rank / np.maximum(deg, 1), 0.0)
        acc = np.zeros(n)
        np.add.at(acc, g.dst, contrib[np.repeat(np.arange(n),
                                                (g.ptr[1:] - g.ptr[:-1]))])
        dangling = rank[deg == 0].sum()
        rank = (1 - damping) / n + damping * (acc + dangling / n)
    return rank


def kcore_ref(g: CSRGraph, k: int) -> np.ndarray:
    """k-core membership by sequential peeling (g symmetric, deduped).

    Repeatedly delete vertices whose remaining degree is < k, decrementing
    each neighbor once per deleted edge.  Returns (V,) int64 in {0, 1}.
    """
    n = g.num_vertices
    deg = (g.ptr[1:] - g.ptr[:-1]).astype(np.int64)
    alive = np.ones(n, bool)
    while True:
        newly = alive & (deg < k)
        if not newly.any():
            break
        alive &= ~newly
        for v in np.flatnonzero(newly):
            for e in range(g.ptr[v], g.ptr[v + 1]):
                deg[g.dst[e]] -= 1
    return alive.astype(np.int64)


def triangles_ref(g: CSRGraph, key: np.ndarray | None = None) -> np.ndarray:
    """Per-vertex triangle counts, each triangle attributed to its
    ``key``-minimum vertex (default: original id order; the engine uses
    placed order, so pass ``pg.place``).  g must be symmetric and deduped;
    ``counts.sum()`` is the total triangle count regardless of ``key``.
    """
    n = g.num_vertices
    key = np.arange(n) if key is None else np.asarray(key)
    adj = [set(g.dst[g.ptr[v]:g.ptr[v + 1]].tolist()) for v in range(n)]
    cnt = np.zeros(n, np.int64)
    for v in range(n):
        for u in adj[v]:
            if key[u] > key[v]:
                for w in adj[u]:
                    if key[w] > key[u] and w in adj[v]:
                        cnt[v] += 1
    return cnt


def spmv_ref(g: CSRGraph, x: np.ndarray) -> np.ndarray:
    """Push-mode SpMV: y[dst] += val * x[src]  (i.e. y = A^T x for CSR-by-src).

    The Dalorex engine propagates along out-edges, so this is the natural
    orientation; callers wanting A x should build the transposed CSR.
    """
    n = g.num_vertices
    src = np.repeat(np.arange(n), (g.ptr[1:] - g.ptr[:-1]))
    y = np.zeros(n, np.float64)
    np.add.at(y, g.dst, g.val.astype(np.float64) * x[src])
    return y
