"""The benchmark's Graph500 generator and its copies of the references.

At small scales on the CPU: the generator is a function of the seed, the
search keys are drawn from the seed, the label permutation is a
bijection, the initiator's quadrant frequencies are the specification's,
the graph is symmetric, loop-free and deduplicated, the copied ``bfs_ref``/``pagerank_ref`` agree with the
program's ``core/reference.py``, and the ``teps`` edge count equals a
brute-force count."""
import numpy as np
import pytest

from bench import graph500, reference
from repro.core import reference as program_reference

SPEC = dict(edgefactor=16, A=0.57, B=0.19, C=0.19)
SEEDS = (0, 7, 2**31 + 11, 5_000_000_000)


def cfg(scale, graph_seed):
    return dict(SPEC, scale=scale, graph_seed=graph_seed)


@pytest.fixture(scope="module")
def graphs():
    return {s: graph500.build(cfg(10, s)) for s in SEEDS}


@pytest.mark.parametrize("seed", SEEDS)
def test_generator_is_a_function_of_the_seed(seed, graphs):
    again = graph500.build(cfg(10, seed))
    g = graphs[seed]
    np.testing.assert_array_equal(again.ptr, g.ptr)
    np.testing.assert_array_equal(again.dst, g.dst)
    others = [s for s in SEEDS if s != seed]
    assert all(graphs[s].num_edges != g.num_edges
               or not np.array_equal(graphs[s].dst, g.dst) for s in others)


@pytest.mark.parametrize("seed", SEEDS)
def test_search_keys_are_drawn_from_the_seed(seed, graphs):
    g = graphs[seed]
    keys = graph500.search_keys(g, 64, seed)
    np.testing.assert_array_equal(keys, graph500.search_keys(g, 64, seed))
    assert len(set(keys.tolist())) == 64
    assert np.all(graph500.degrees(g)[keys] > 0)
    other = graph500.search_keys(g, 64, seed + 1)
    assert not np.array_equal(keys, other)


@pytest.mark.parametrize("seed", SEEDS)
def test_permutation_is_a_bijection(seed):
    p = graph500.vertex_permutation(seed, 12)
    np.testing.assert_array_equal(np.sort(p), np.arange(1 << 12))
    assert not np.array_equal(p, np.arange(1 << 12))


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_quadrant_frequencies_match_the_specification(seed):
    import jax.numpy as jnp

    scale = 12
    i, j = map(np.asarray, graph500.kronecker_edges(
        jnp.asarray(graph500.seed_words(seed, graph500.GRAPH_STREAM)), scale,
        16, 0.57, 0.19, 0.19))
    bits = np.arange(scale)
    ii = (i[:, None] >> bits) & 1
    jj = (j[:, None] >> bits) & 1
    n = ii.size
    for (a, b), p in {(0, 0): 0.57, (0, 1): 0.19, (1, 0): 0.19,
                      (1, 1): 0.05}.items():
        freq = np.count_nonzero((ii == a) & (jj == b)) / n
        # 5 binomial standard deviations of n draws
        assert abs(freq - p) < 5 * np.sqrt(p * (1 - p) / n), (a, b, freq)


@pytest.mark.parametrize("seed", SEEDS)
def test_graph_is_symmetric_loop_free_and_deduplicated(seed, graphs):
    g = graphs[seed]
    src = np.repeat(np.arange(g.num_vertices), np.diff(g.ptr))
    assert not np.any(src == g.dst)
    key = src * g.num_vertices + g.dst
    assert np.all(np.diff(key) > 0)          # sorted and unique
    rev = np.sort(g.dst * g.num_vertices + src)
    np.testing.assert_array_equal(rev, key)


@pytest.mark.parametrize("seed", SEEDS)
def test_copied_references_agree_with_the_program(seed, graphs):
    g = graphs[seed]
    deg = graph500.degrees(g)
    for root in np.flatnonzero(deg > 0)[:3]:
        np.testing.assert_array_equal(reference.bfs_ref(g, int(root)),
                                      program_reference.bfs_ref(g, int(root)))
    np.testing.assert_allclose(reference.pagerank_ref(g, iters=3),
                               program_reference.pagerank_ref(g, iters=3),
                               rtol=1e-12)


@pytest.mark.parametrize("seed", SEEDS)
def test_teps_edge_count_equals_brute_force(seed, graphs):
    g = graphs[seed]
    deg = graph500.degrees(g)
    root = int(np.flatnonzero(deg > 0)[seed % 5])
    levels = reference.bfs_ref(g, root)
    count = 0
    for u in range(g.num_vertices):
        for v in g.dst[g.ptr[u]:g.ptr[u + 1]]:
            if u < v and np.isfinite(levels[u]) and np.isfinite(levels[v]):
                count += 1
    assert graph500.component_edges(g, levels) == count > 0
