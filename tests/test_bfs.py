"""Single-device BFS vs host oracle + Graph500 validator rules."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import bfs, validate
from repro.graphgen import builder, kronecker


def _device_graph(g):
    return jnp.asarray(g.src.astype(np.int32)), jnp.asarray(g.dst.astype(np.int32))


@pytest.mark.parametrize("scale", [6, 9])
def test_bfs_levels_match_reference(scale):
    g = builder.build_csr(kronecker.kronecker_edges(scale, seed=2), n=1 << scale)
    src, dst = _device_graph(g)
    res = bfs.bfs(src, dst, jnp.int32(0), g.n)
    ref = validate.reference_bfs(g, 0)
    np.testing.assert_array_equal(np.asarray(res.level), ref)
    v = validate.validate_bfs_tree(g, np.asarray(res.parent), 0, np.asarray(res.level))
    assert v.ok, v.failures


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1 << 16), root=st.integers(0, 255))
def test_bfs_property_random_graphs(seed, root):
    """For arbitrary random graphs the BFS tree passes all 5 rules and
    levels equal the oracle's."""
    rng = np.random.default_rng(seed)
    n = 256
    m = rng.integers(1, 2048)
    edges = rng.integers(0, n, size=(m, 2))
    g = builder.build_csr(edges, n=n)
    src, dst = _device_graph(g)
    res = bfs.bfs(src, dst, jnp.int32(root), g.n)
    ref = validate.reference_bfs(g, root)
    np.testing.assert_array_equal(np.asarray(res.level), ref)
    v = validate.validate_bfs_tree(g, np.asarray(res.parent), root, np.asarray(res.level))
    assert v.ok, v.failures


def test_bfs_levels_sizes():
    g = builder.build_csr(kronecker.kronecker_edges(8, seed=1), n=256)
    src, dst = _device_graph(g)
    res, sizes = bfs.bfs_levels(src, dst, jnp.int32(0), g.n, max_levels=16)
    sizes = np.asarray(sizes)
    n_reached = int((np.asarray(res.level) >= 0).sum())
    assert sizes.sum() + 1 == n_reached  # root not counted in level frontiers


def test_bfs_max_levels_guard():
    """Regression: an adversarial high-diameter edge list (a path) used to
    keep bfs()'s while_loop spinning for O(n) levels — the single-device
    drivers now honor the same depth cap as DistBFSConfig.max_levels."""
    n = 256
    path = np.stack([np.arange(n - 1), np.arange(1, n)], axis=1)
    g = builder.build_csr(path, n=n)
    src, dst = _device_graph(g)
    res = bfs.bfs(src, dst, jnp.int32(0), g.n, max_levels=10)
    assert int(res.n_levels) == 10
    level = np.asarray(res.level)
    parent = np.asarray(res.parent)
    # vertices within the cap are correct; the rest stay unreached
    np.testing.assert_array_equal(level[:11], np.arange(11))
    assert np.all(level[11:] == -1) and np.all(parent[11:] == -1)
    # same cap semantics from the scan-based driver
    res_l, sizes = bfs.bfs_levels(src, dst, jnp.int32(0), g.n, max_levels=10)
    np.testing.assert_array_equal(np.asarray(res_l.level), level)
    assert int(res_l.n_levels) == 10
    # the default cap matches the distributed driver's
    from repro.core.distributed_bfs import DistBFSConfig

    full = bfs.bfs(src, dst, jnp.int32(0), g.n, max_levels=DistBFSConfig().max_levels)
    assert int(full.n_levels) == 64 and int(np.asarray(full.level).max()) == 64


def test_validator_catches_corruption():
    g = builder.build_csr(kronecker.kronecker_edges(8, seed=3), n=256)
    src, dst = _device_graph(g)
    res = bfs.bfs(src, dst, jnp.int32(0), g.n)
    parent = np.asarray(res.parent).copy()
    reached = np.nonzero(parent >= 0)[0]
    victim = reached[-1]
    # rule 5 violation: parent not adjacent
    bad = parent.copy()
    non_nbrs = np.setdiff1d(reached, np.append(g.neighbors(victim), victim))
    if non_nbrs.size and victim != 0:
        bad[victim] = non_nbrs[0]
        assert not validate.validate_bfs_tree(g, bad, 0).ok
    # rule 1 violation: cycle
    bad = parent.copy()
    a, b = reached[1], reached[2]
    bad[a], bad[b] = b, a
    assert not validate.validate_bfs_tree(g, bad, 0).ok
    # rule 4 violation: claim an unreached vertex
    unreached = np.nonzero(parent < 0)[0]
    if unreached.size:
        bad = parent.copy()
        bad[unreached[0]] = 0
        assert not validate.validate_bfs_tree(g, bad, 0).ok


def test_edge_membership_and_reference_bfs_vectorized():
    """The vectorized host checks agree with per-vertex python loops: the
    CSR edge lookup behind rule 5, and the reference BFS levels."""
    g = builder.build_csr(kronecker.kronecker_edges(9, seed=5), n=512)
    rng = np.random.default_rng(0)
    u = rng.integers(0, g.n, 4000)
    v = np.where(rng.random(4000) < 0.5, rng.integers(0, g.n, 4000),
                 g.col_idx[np.minimum(g.row_ptr[u], g.m - 1)])
    expect = np.array([b in set(g.neighbors(a).tolist()) for a, b in zip(u, v)])
    np.testing.assert_array_equal(validate._edges_exist(g, u, v), expect)
    root = int(np.argmax(g.degrees()))
    level = np.full(g.n, -1)
    level[root], frontier, d = 0, [root], 0
    while frontier:
        d += 1
        nxt = sorted({int(w) for x in frontier for w in g.neighbors(x) if level[w] < 0})
        level[nxt] = d
        frontier = nxt
    np.testing.assert_array_equal(validate.reference_bfs(g, root), level)


def test_traversed_edges_teps_numerator():
    g = builder.build_csr(kronecker.kronecker_edges(8, seed=1), n=256)
    src, dst = _device_graph(g)
    root = int(np.argmax(g.degrees()))  # Graph500 samples roots with deg > 0
    res = bfs.bfs(src, dst, jnp.int32(root), g.n)
    te = validate.traversed_edges(g, np.asarray(res.parent))
    assert 0 < te <= g.m // 2


def test_partition_2d_covers_all_edges():
    g = builder.build_csr(kronecker.kronecker_edges(8, seed=5), n=256)
    from repro.core import csr as csrmod

    bg = csrmod.partition_2d(g, rows=2, cols=2, chunk_multiple=64, e_cap_multiple=64)
    part = bg.part
    total = int(bg.e_counts.sum())
    assert total == g.m  # every symmetric edge lands in exactly one block
    # local indices decode back to the original edge multiset
    rebuilt = []
    for i in range(2):
        for j in range(2):
            sl = bg.src_local[i, j]
            dl = bg.dst_local[i, j]
            mask = sl < part.n_c
            rebuilt.append(
                np.stack([sl[mask] + j * part.n_c, dl[mask] + i * part.n_r], 1)
            )
    rebuilt = np.concatenate(rebuilt)
    orig = np.stack([g.src, g.dst], 1)
    assert np.array_equal(
        rebuilt[np.lexsort(rebuilt.T)], orig[np.lexsort(orig.T)]
    )
    # transpose permutation is a bijection
    perm = part.transpose_perm()
    assert sorted(d for _, d in perm) == list(range(4))
