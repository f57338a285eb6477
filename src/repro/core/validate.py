"""Graph500 BFS-tree validation — the benchmark's 5 rules (paper Alg. 1 l.5).

Host-side (numpy) so it is independent of the JAX implementation under test.
The five rules, per the Graph500 specification:

  1. the BFS tree is a tree and does not contain cycles;
  2. each tree edge connects vertices whose BFS levels differ by exactly one;
  3. every edge in the input graph connects vertices whose levels differ by
     at most one, or both endpoints are unreached (same component check);
  4. the BFS tree spans exactly the connected component of the root;
  5. a node and its BFS parent are joined by an edge of the original graph.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.graphgen.builder import CSRGraph


@dataclasses.dataclass(frozen=True)
class ValidationResult:
    ok: bool
    failures: tuple[str, ...]
    n_reached: int
    n_tree_edges: int

    def __bool__(self) -> bool:
        return self.ok


def _ranges(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Concatenated index ranges ``[lo_k, hi_k)`` — a vectorized ragged
    gather, so frontier expansions cost no Python loop per vertex."""
    lens = hi - lo
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    ends = np.cumsum(lens)
    return np.arange(total, dtype=np.int64) + np.repeat(lo - (ends - lens), lens)


def compute_levels(parent: np.ndarray, root: int, max_iter: int | None = None) -> np.ndarray:
    """Levels by pointer-jumping over parent links; -2 marks a cycle/overflow."""
    n = parent.shape[0]
    level = np.full(n, -1, dtype=np.int64)
    level[root] = 0
    reached = parent >= 0
    frontier = np.array([root])
    depth = 0
    max_iter = max_iter or n
    children = np.argsort(parent[reached], kind="stable")
    nodes = np.nonzero(reached)[0][children]
    parents_sorted = parent[nodes]
    while frontier.size and depth < max_iter:
        depth += 1
        lo = np.searchsorted(parents_sorted, frontier, side="left")
        hi = np.searchsorted(parents_sorted, frontier, side="right")
        nxt = nodes[_ranges(lo, hi)]
        nxt = nxt[level[nxt] < 0]
        level[nxt] = depth
        frontier = nxt
    return level


def _edges_exist(g: CSRGraph, u: np.ndarray, v: np.ndarray) -> np.ndarray:
    """Whether each ``(u, v)`` is an edge: a vectorized binary search of
    ``v`` in row ``u`` of the CSR (``build_csr`` sorts every row)."""
    lo, hi = g.row_ptr[u].copy(), g.row_ptr[u + 1]
    end = hi.copy()
    while True:
        active = lo < hi
        if not active.any():
            break
        mid = (lo + hi) // 2
        right = g.col_idx[np.minimum(mid, g.m - 1)] < v
        lo = np.where(active & right, mid + 1, lo)
        hi = np.where(active & ~right, mid, hi)
    return (lo < end) & (g.col_idx[np.minimum(lo, g.m - 1)] == v)


def validate_bfs_tree(
    g: CSRGraph, parent: np.ndarray, root: int, level: np.ndarray | None = None
) -> ValidationResult:
    parent = np.asarray(parent, dtype=np.int64)[: g.n]
    n = g.n
    failures: list[str] = []

    reached = parent >= 0
    if not reached[root] or parent[root] != root:
        failures.append("rule1: root parent must be root itself")

    lv = compute_levels(parent, root)
    # Rule 1: no cycles — every reached vertex must get a finite level.
    stuck = reached & (lv < 0)
    if stuck.any():
        failures.append(f"rule1: {int(stuck.sum())} reached vertices not connected to root (cycle)")

    if level is not None:
        level = np.asarray(level, dtype=np.int64)[:n]
        mism = reached & (lv >= 0) & (level != lv)
        if mism.any():
            failures.append(f"levels: {int(mism.sum())} reported levels disagree with tree depth")

    # Rule 2 & 5: tree edges exist in graph and span exactly one level.
    tree_v = np.nonzero(reached & (np.arange(n) != root))[0]
    tree_u = parent[tree_v]
    if tree_v.size:
        exists = _edges_exist(g, tree_u, tree_v)
        if not exists.all():
            failures.append(f"rule5: {int((~exists).sum())} tree edges missing from graph")
        dl = lv[tree_v] - lv[tree_u]
        bad = (lv[tree_v] >= 0) & (lv[tree_u] >= 0) & (dl != 1)
        if bad.any():
            failures.append(f"rule2: {int(bad.sum())} tree edges do not span exactly one level")

    # Rule 3: every graph edge spans <= 1 level, both-or-neither reached.
    ru, rv = reached[g.src], reached[g.dst]
    cross = int(np.count_nonzero(ru != rv))
    if cross:
        failures.append(f"rule4: {cross} edges cross the reached boundary")
    lv32 = lv.astype(np.int32)  # levels are < n <= 2**31
    span = np.abs(lv32[g.src] - lv32[g.dst]) > 1
    far = int(np.count_nonzero(span & ru & rv))
    if far:
        failures.append(f"rule3: {far} graph edges span more than one level")

    # Rule 4: reached set == connected component of root (computed by ref BFS).
    comp = reference_bfs(g, root) >= 0
    if (reached != comp).any():
        failures.append(
            f"rule4: reached set differs from root component by {int((reached != comp).sum())}"
        )

    return ValidationResult(
        ok=not failures,
        failures=tuple(failures),
        n_reached=int(reached.sum()),
        n_tree_edges=int(tree_v.size),
    )


def reference_bfs(g: CSRGraph, root: int) -> np.ndarray:
    """Plain host BFS returning levels (-1 unreached) — the oracle."""
    level = np.full(g.n, -1, dtype=np.int64)
    level[root] = 0
    frontier = np.array([root], dtype=np.int64)
    d = 0
    while frontier.size:
        d += 1
        nbrs = g.col_idx[_ranges(g.row_ptr[frontier], g.row_ptr[frontier + 1])]
        new = np.zeros(g.n, dtype=bool)
        new[nbrs[level[nbrs] < 0]] = True
        frontier = np.flatnonzero(new)
        level[frontier] = d
    return level


def reference_sssp(g: CSRGraph, root: int, max_weight: int = 31) -> np.ndarray:
    """Host Dijkstra over the hashed edge weights — the SSSP oracle.

    Weights come from :func:`repro.core.algebra.edge_weight` with ``xp=np``
    so the uint32 avalanche mix wraps identically to the in-graph version
    and the distance comparison is exact.  Unreached vertices hold
    ``repro.comm.formats.INF`` to match the device driver's encoding.
    """
    import heapq

    from repro.comm.formats import INF
    from repro.core.algebra import edge_weight

    dist = np.full(g.n, np.iinfo(np.int64).max, dtype=np.int64)
    dist[root] = 0
    pq = [(0, int(root))]
    while pq:
        du, u = heapq.heappop(pq)
        if du > dist[u]:
            continue
        nbrs = g.col_idx[g.row_ptr[u] : g.row_ptr[u + 1]]
        if nbrs.size == 0:
            continue
        w = edge_weight(
            np.full(nbrs.size, u, np.int64), nbrs.astype(np.int64),
            max_weight=max_weight, xp=np,
        ).astype(np.int64)
        for v, nd in zip(nbrs, du + w):
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(pq, (int(nd), int(v)))
    return np.where(dist == np.iinfo(np.int64).max, np.int64(INF), dist)


def reference_cc(g: CSRGraph) -> np.ndarray:
    """Union-find min-label components — the connected-components oracle.

    Returns, per vertex, the minimum vertex id of its component (the fixed
    point of min-label propagation, so it compares exactly against the
    ``cc`` algebra's value plane)."""
    parent = np.arange(g.n, dtype=np.int64)

    def find(x: int) -> int:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for u, v in zip(g.src, g.dst):
        ru, rv = find(int(u)), find(int(v))
        if ru != rv:
            parent[max(ru, rv)] = min(ru, rv)
    roots = np.array([find(i) for i in range(g.n)])
    # path-compressed roots ARE the min ids: union always keeps the smaller
    return roots


def reference_pagerank(
    g: CSRGraph, n: int | None = None, damping: float = 0.85,
    tol: float = 1e-4, max_iter: int = 500,
) -> np.ndarray:
    """Host power iteration — the PageRank oracle.

    Matches the ``pagerank`` algebra's conventions exactly: uniform init
    1/n over the (padded) vertex count ``n``, dangling mass NOT
    redistributed, termination on global L1 step-residual <= ``tol``.
    Pass the driver's padded ``part.n`` as ``n`` to compare elementwise.
    """
    n = g.n if n is None else n
    src = np.concatenate([g.src, g.dst]).astype(np.int64)
    dst = np.concatenate([g.dst, g.src]).astype(np.int64)
    deg = np.zeros(n, np.int64)
    np.add.at(deg, src, 1)
    v = np.full(n, 1.0 / n)
    for _ in range(max_iter):
        contrib = np.where(deg > 0, v / np.maximum(deg, 1), 0.0)
        nxt = np.full(n, (1.0 - damping) / n)
        np.add.at(nxt, dst, damping * contrib[src])
        done = np.abs(nxt - v).sum() <= tol
        v = nxt
        if done:
            break
    return v


def traversed_edges(g: CSRGraph, parent: np.ndarray) -> int:
    """TEPS numerator: input edges with both endpoints in the traversed
    component (Graph500 counts undirected input edges once)."""
    reached = np.asarray(parent)[: g.n] >= 0
    # m_input directed input edges were symmetrized; count input edges whose
    # endpoints are reached.  Approximation per spec: use input edge count
    # scaled by reached fraction of edges in the CSR.
    both = reached[g.src] & reached[g.dst]
    return int(both.sum()) // 2
