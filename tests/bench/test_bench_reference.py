"""The yardstick's arithmetic on small graphs: the generator, root
sampling, the plain reference BFS, the parent rules and the Graph500
TEPS count, each against a brute-force count."""

import collections

import numpy as np
import pytest

from bench import kronecker, reference


def _bfs(edges, n, root):
    adj = collections.defaultdict(set)
    for u, v in edges:
        adj[int(u)].add(int(v))
        adj[int(v)].add(int(u))
    level = [-1] * n
    level[root] = 0
    queue = collections.deque([root])
    while queue:
        u = queue.popleft()
        for v in adj[u]:
            if level[v] < 0:
                level[v] = level[u] + 1
                queue.append(v)
    return np.array(level)


@pytest.fixture(scope="module")
def graph():
    scale = 9
    edges = kronecker.kronecker_edges(7, scale, 16)
    return edges, 1 << scale


def test_generator_is_seeded_and_graph500_shaped(graph):
    edges, n = graph
    assert edges.shape == (16 * n, 2) and edges.dtype == np.int32
    assert edges.min() >= 0 and edges.max() < n
    np.testing.assert_array_equal(edges, kronecker.kronecker_edges(7, 9, 16))
    other = kronecker.kronecker_edges(2**40 + 7, 9, 16)  # wider than 32 bits
    assert not np.array_equal(edges, other)
    # Kronecker skew: a few hubs hold a large share of the endpoints
    deg = np.bincount(edges.ravel(), minlength=n)
    assert np.sort(deg)[-n // 100:].sum() > 0.1 * deg.sum()


def test_roots_are_valid_distinct_and_seeded(graph):
    edges, n = graph
    deg = kronecker.degrees(edges, n)
    roots = kronecker.valid_roots(deg, 64, 5)
    assert len(set(roots.tolist())) == 64 and (deg[roots] > 0).all()
    np.testing.assert_array_equal(roots, kronecker.valid_roots(deg, 64, 5))
    with pytest.raises(ValueError):
        kronecker.valid_roots(deg, int((deg > 0).sum()) + 1, 5)


def test_reference_levels_equal_a_plain_queue_bfs(graph):
    edges, n = graph
    ref = reference.Reference(edges, n, threads=2)
    roots = kronecker.valid_roots(kronecker.degrees(edges, n), 70, 3)  # > 64: two words
    levels = ref.levels(roots)
    for k in (0, 1, 65, 69):
        np.testing.assert_array_equal(levels[k], _bfs(edges, n, int(roots[k])))


def test_teps_count_is_input_tuples_inside_the_component():
    edges = np.array([[0, 1], [1, 2], [2, 2], [1, 0], [3, 4], [5, 5], [2, 0]], np.int32)
    n = 8
    ref = reference.Reference(edges, n, threads=1)
    for root in range(n):
        level = ref.levels([root])[0]
        brute = sum(1 for u, v in edges if level[u] >= 0 and level[v] >= 0)
        assert ref.traversed(level) == brute
    assert ref.traversed(ref.levels([0])[0]) == 5  # four edges and a self-loop


def test_parent_rules(graph):
    edges, n = graph
    ref = reference.Reference(edges, n, threads=2)
    root = int(kronecker.valid_roots(kronecker.degrees(edges, n), 1, 1)[0])
    level = ref.levels([root])[0]
    parent = ref.parents(root, level)
    assert ref.bad_parents(root, parent, level) == 0
    v = int(np.flatnonzero(level == 2)[0])
    wrong = parent.copy()
    wrong[v] = root  # the root is two levels up, not one
    assert ref.bad_parents(root, wrong, level) == 1
    wrong = parent.copy()
    wrong[np.flatnonzero(level < 0)[0]] = root  # an unreached vertex with a parent
    assert ref.bad_parents(root, wrong, level) == 1
    up = np.flatnonzero(level == level[v] - 1)
    not_adjacent = [u for u in up if int(u) not in _neighbours(edges, v)]
    wrong = parent.copy()
    wrong[v] = not_adjacent[0]  # one level up, but no edge
    assert ref.bad_parents(root, wrong, level) == 1


def _neighbours(edges, v):
    return {int(b) for a, b in edges if a == v} | {int(a) for a, b in edges if b == v}


def test_compare_counts_every_wrong_answer(graph):
    edges, n = graph
    ref = reference.Reference(edges, n, threads=2)
    roots = [int(r) for r in kronecker.valid_roots(kronecker.degrees(edges, n), 3, 2)]
    good = [(ref.parents(r, lv), lv) for r, lv in zip(roots, ref.levels(roots))]
    verdict = reference.compare(ref, roots, good)
    assert verdict.correct and verdict.wrong_levels == 0
    assert verdict.traversed == [ref.traversed(lv) for _, lv in good]
    level = good[1][1].copy()
    level[level == level.max()] = -1
    bad = [good[0], (good[1][0], level), None]
    verdict = reference.compare(ref, roots, bad)
    assert not verdict.correct
    assert verdict.wrong_searches == 2 and verdict.missing == 1
    assert verdict.wrong_levels == int((good[1][1] == good[1][1].max()).sum())
