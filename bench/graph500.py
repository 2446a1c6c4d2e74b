"""Graph500 Kronecker graphs, made on the device from a seed.

The generator follows the Graph500 specification, section "Graph
generation" (its reference ``kronecker_generator``): ``edgefactor * 2**scale``
edge tuples, each built bit by bit from the initiator probabilities
A, B, C (D = 1 - A - B - C), then a random permutation of the vertex
labels.  The edge order is not shuffled: the graph is built as CSR, which
forgets it.  Kernel 1's graph, as this benchmark serves it, is that edge
list symmetrized (every tuple in both directions), deduplicated and
without self-loops, unweighted.

A configuration's graph, tuples and labels both, is drawn from its
``graph_seed``, as Graph500 builds one graph per run of its benchmark;
a run's ``--seed`` draws the search keys (``search_keys``).  Labels
drawn per seed changed the engine's work: a 2-epoch PageRank call at
scale 17 took 2086 to 2226 rounds over four labellings of one graph.

The draws run on the device in one jitted call; the host only compacts
the sorted, deduplicated pairs into CSR.  Nothing here imports the
program: the CSR is the program's ``CSRGraph`` container, filled by this
code.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# Streams of one seed: each use of a seed draws from its own stream.
GRAPH_STREAM, TRAFFIC_STREAM, CONTROL_STREAM = 1, 2, 3


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words (a threefry key) for ``stream`` of ``seed``; any
    whole number is a seed, including ones past 32 bits."""
    return np.random.SeedSequence([stream, seed % (1 << 64)]).generate_state(
        2, np.uint32)


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [stream, seed % (1 << 64)]))


def _kronecker(key, scale: int, edgefactor: int, a: float, b: float,
               c: float):
    """(i, j) int32 endpoints of the raw tuples, before the permutation."""
    m = edgefactor << scale
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab

    def level(bit, ij):
        i, j = ij
        k1, k2 = jax.random.split(jax.random.fold_in(key, bit))
        ii = jax.random.uniform(k1, (m,)) > ab
        jj = jax.random.uniform(k2, (m,)) > jnp.where(ii, c_norm, a_norm)
        return (i | (ii.astype(jnp.int32) << bit),
                j | (jj.astype(jnp.int32) << bit))

    zero = jnp.zeros(m, jnp.int32)
    return jax.lax.fori_loop(0, scale, level, (zero, zero))


@partial(jax.jit, static_argnames=("scale", "edgefactor", "a", "b", "c"))
def kronecker_edges(key_words, scale, edgefactor, a, b, c):
    """The raw tuples, unpermuted (tests read the initiator from them)."""
    return _kronecker(jax.random.wrap_key_data(key_words), scale,
                      edgefactor, a, b, c)


@partial(jax.jit, static_argnames=("scale", "edgefactor", "a", "b", "c"))
def _symmetric_pairs(key_words, scale, edgefactor, a, b, c):
    """Permuted, symmetrized tuples sorted by (src, dst), and a mask of
    the first copy of each that is not a self-loop."""
    kg, kp = jax.random.split(jax.random.wrap_key_data(key_words))
    i, j = _kronecker(kg, scale, edgefactor, a, b, c)
    perm = jax.random.permutation(kp, 1 << scale).astype(jnp.int32)
    i, j = perm[i], perm[j]
    s, d = jax.lax.sort((jnp.concatenate([i, j]), jnp.concatenate([j, i])),
                        num_keys=2)
    first = jnp.concatenate([jnp.ones(1, bool),
                             (s[1:] != s[:-1]) | (d[1:] != d[:-1])])
    return s, d, first & (s != d)


def vertex_permutation(seed: int, scale: int) -> np.ndarray:
    """The label permutation the graph of ``seed`` applies (tests)."""
    kg, kp = jax.random.split(jax.random.wrap_key_data(
        jnp.asarray(seed_words(seed, GRAPH_STREAM))))
    return np.asarray(jax.random.permutation(kp, 1 << scale))


def build(cfg: dict):
    """The graph of ``cfg`` (its scale, initiator and ``graph_seed``), as
    a ``CSRGraph``."""
    from repro.core.graph import CSRGraph

    scale = int(cfg["scale"])
    s, d, keep = jax.device_get(_symmetric_pairs(
        jnp.asarray(seed_words(int(cfg["graph_seed"]), GRAPH_STREAM)), scale,
        int(cfg["edgefactor"]), float(cfg["A"]), float(cfg["B"]),
        float(cfg["C"])))
    src, dst = s[keep], d[keep]
    n = 1 << scale
    ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=ptr[1:])
    return CSRGraph(ptr, dst.astype(np.int64),
                    np.ones(len(dst), np.float32))


def degrees(g) -> np.ndarray:
    return np.diff(g.ptr)


def search_keys(g, count: int, seed: int) -> np.ndarray:
    """``count`` distinct search keys of nonzero degree drawn from
    ``seed``, in the order drawn (Graph500 draws 64)."""
    nz = np.flatnonzero(degrees(g) > 0)
    rng = seed_rng(seed, TRAFFIC_STREAM)
    return rng.choice(nz, min(count, nz.size), replace=False)


def component_edges(g, levels: np.ndarray) -> int:
    """Graph500's traversed edges of one search: the undirected edges of
    the (symmetric, loop-free, deduplicated) graph inside the component
    the search reached, counted from the graph and the levels alone."""
    reached = np.isfinite(levels)
    return int(degrees(g)[reached].sum()) // 2
