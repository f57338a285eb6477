"""Edge list → CSR construction (paper "Kernel 1": graph construction).

The Graph 500 benchmark times graph construction separately from BFS; this
module is that kernel.  It produces a :class:`CSRGraph` holding CSR arrays
plus the symmetric COO edge arrays the edge-centric BFS consumes.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Compressed-sparse-row graph + symmetric COO view.

    Attributes:
      n: vertex count.
      row_ptr: (n+1,) int64 CSR offsets.
      col_idx: (m,) int32 CSR adjacency (deduped, self-loop-free, symmetric,
        sorted within each row).
      src/dst: (m,) int32 COO view of the same edges (sorted by src).
      m_input: number of *input* (pre-dedup, directed) edges — the TEPS
        denominator uses input edges within the traversed component.
    """

    n: int
    row_ptr: np.ndarray
    col_idx: np.ndarray
    src: np.ndarray
    dst: np.ndarray
    m_input: int

    @property
    def m(self) -> int:
        return int(self.col_idx.shape[0])

    def degrees(self) -> np.ndarray:
        return np.diff(self.row_ptr)

    def neighbors(self, v: int) -> np.ndarray:
        return self.col_idx[self.row_ptr[v] : self.row_ptr[v + 1]]


def symmetrize(edges: np.ndarray) -> np.ndarray:
    """Append reversed edges: BFS treats the Graph500 graph as undirected."""
    return np.concatenate([edges, edges[:, ::-1]], axis=0)


def build_csr(
    edges: np.ndarray,
    n: int | None = None,
    drop_self_loops: bool = True,
    dedupe: bool = True,
    symmetrize_edges: bool = True,
) -> CSRGraph:
    """Build a symmetric CSR graph from an (m, 2) directed edge array.

    Pass ``symmetrize_edges=False`` when the input is already symmetric
    (e.g. rebuilding from another CSRGraph's src/dst arrays)."""
    edges = np.asarray(edges, dtype=np.int64)
    m_input = int(edges.shape[0])
    if n is None:
        n = int(edges.max()) + 1 if edges.size else 0

    sym = symmetrize(edges) if symmetrize_edges else edges
    if drop_self_loops:
        sym = sym[sym[:, 0] != sym[:, 1]]
    # Sort by (src, dst) as one int64 key (src * n + dst); dedupe.
    key = sym[:, 0] * max(n, 1) + sym[:, 1]
    del sym
    key.sort()
    if dedupe and key.size:
        keep = np.ones(key.size, dtype=bool)
        np.not_equal(key[1:], key[:-1], out=keep[1:])
        key = key[keep]

    src = (key // max(n, 1)).astype(np.int32)
    dst = (key % max(n, 1)).astype(np.int32)
    counts = np.bincount(src, minlength=n)
    row_ptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=row_ptr[1:])
    return CSRGraph(
        n=n, row_ptr=row_ptr, col_idx=dst.copy(), src=src, dst=dst, m_input=m_input
    )


def relabel_by_degree(g: CSRGraph) -> tuple[CSRGraph, np.ndarray]:
    """Paper §3.1 "vertex sorting": relabel vertices by descending degree.

    High-degree vertices get small ids, so the frontier's sorted id sequence
    concentrates near zero with small gaps — exactly the numerical property
    the paper's delta+bitpack codec exploits (§5.4.1).  Returns the relabeled
    graph and the permutation ``new_id = perm[old_id]``.
    """
    deg = g.degrees()
    order = np.argsort(-deg, kind="stable")  # old ids in new order
    perm = np.empty_like(order)
    perm[order] = np.arange(g.n)
    new_edges = np.stack([perm[g.src], perm[g.dst]], axis=1)
    rebuilt = build_csr(
        new_edges, n=g.n, drop_self_loops=False, dedupe=False, symmetrize_edges=False
    )
    # m_input is a property of the original generator stream; preserve it.
    rebuilt = dataclasses.replace(rebuilt, m_input=g.m_input)
    return rebuilt, perm


# ---------------------------------------------------------------------------
# ELL / hybrid local-expansion containers (built at partition time)
# ---------------------------------------------------------------------------


def _round_up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def ell_from_edges(
    src: np.ndarray,
    dst: np.ndarray,
    n_rows: int,
    n_cols: int,
    k: int,
    width: int | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Degree-split one local COO edge block at ``k``.

    Rows (destinations) with degree <= ``k`` move *entirely* into a dense
    destination-major ``(n_rows, width)`` ELL slab (sentinel-padded with
    ``n_cols``, which never hits a frontier bitmap); heavier rows keep all
    their edges in the returned COO residue — each row's edge set lives in
    exactly one structure, so ``min(slab result, residue result)`` equals
    the flat minimum over the union.  ``width`` (defaults to ``k``)
    lets hybrid blocks share one slab width across blocks with different
    per-block splits.  Edges at the (``n_cols``, ``n_rows``) sentinels are
    dropped, mirroring how the gathers mask them.
    """
    width = k if width is None else width
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    valid = (src < n_cols) & (dst < n_rows)
    s, d = src[valid], dst[valid]
    deg = np.bincount(d, minlength=n_rows)
    in_slab = deg[d] <= k
    nbr = np.full((n_rows, max(width, 1)), n_cols, np.int32)
    sd, ss = d[in_slab], s[in_slab]
    order = np.argsort(sd, kind="stable")
    sd, ss = sd[order], ss[order]
    starts = np.searchsorted(sd, np.arange(n_rows))
    rank = np.arange(sd.size) - starts[sd]
    nbr[sd, rank] = ss
    # the residue is destination-major (by destination, then source), the
    # layout the COO expansion reads; partition blocks already are
    rs, rd = s[~in_slab], d[~in_slab]
    key = rd * (n_cols + 1) + rs
    if (key[1:] < key[:-1]).any():
        order = np.argsort(key, kind="stable")
        rs, rd = rs[order], rd[order]
    return nbr, rs.astype(np.int32), rd.astype(np.int32)


def select_split_k(
    degrees: np.ndarray, waste_budget: float = 0.5, multiple: int = 8
) -> int:
    """Pick the hybrid degree split from a block's degree histogram.

    Chooses the largest ``k`` (a ``multiple``-aligned slab width) whose ELL
    slab keeps padding waste under the budget, where waste is the fraction
    of slab slots holding sentinels:

        waste(k) = 1 - (edges of rows with degree <= k) / (n_rows * k)

    Covered edges grow sublinearly in ``k`` on skewed degree distributions
    (hubs are few), so the largest affordable ``k`` moves the most edges
    onto the dense slab while the hub residue stays COO.  Falls back to the
    smallest slab when even that exceeds the budget (near-empty blocks).
    """
    deg = np.asarray(degrees)
    n_rows = int(deg.size)
    max_deg = int(deg.max(initial=0))
    if n_rows == 0 or max_deg == 0:
        return multiple
    hist = np.bincount(deg)
    covered = np.cumsum(np.arange(hist.size) * hist)  # edges of rows deg<=k
    best = multiple
    for k in range(multiple, max_deg + multiple, multiple):
        if covered[min(k, hist.size - 1)] >= (1.0 - waste_budget) * n_rows * k:
            best = k
    return best


def edge_degrees(
    src: np.ndarray, dst: np.ndarray, n_rows: int, n_cols: int
) -> np.ndarray:
    """Per-destination degree over the valid (non-sentinel) edges — THE
    valid-edge convention every container builder shares."""
    src, dst = np.asarray(src), np.asarray(dst)
    valid = (src < n_cols) & (dst < n_rows)
    return np.bincount(dst[valid], minlength=n_rows)[:n_rows]


def degree_offsets(deg: np.ndarray) -> np.ndarray:
    """(..., n_rows + 1) int32 row offsets of destination-major edge arrays
    from their (..., n_rows) degree histograms: each row's first edge, as a
    binary search per row over the arrays gives it, with the sentinel tail
    past the last row (the last offset is the valid edge count)."""
    deg = np.asarray(deg)
    out = np.zeros(deg.shape[:-1] + (deg.shape[-1] + 1,), np.int32)
    np.cumsum(deg, axis=-1, out=out[..., 1:])
    return out


def ell_graph_arrays(
    src: np.ndarray, dst: np.ndarray, n: int, deg_multiple: int = 8
) -> tuple[np.ndarray, int]:
    """Whole-graph ELL slab for the single-device driver.

    ``k`` covers the heaviest row (rounded to a multiple of 8),
    so the residue is empty — the pure-ELL backend.  Returns (slab, k).
    """
    k = _round_up(max(int(edge_degrees(src, dst, n, n).max(initial=1)), 1),
                  deg_multiple)
    nbr, res_s, _ = ell_from_edges(src, dst, n, n, k)
    assert res_s.size == 0, "pure ELL must cover every row"
    return nbr, k


def hybrid_graph_arrays(
    src: np.ndarray,
    dst: np.ndarray,
    n: int,
    waste_budget: float = 0.5,
    split_k: int | None = None,
    deg_multiple: int = 8,
    res_multiple: int = 1024,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Whole-graph hybrid COO/ELL split for the single-device driver.

    Returns (slab, residue src, residue dst, residue row offsets, k); the
    residue arrays are sentinel-padded ((n, n)) to a static
    ``res_multiple`` capacity, and its row offsets come from the degree
    histogram (rows on the slab are empty in the residue).
    """
    deg = edge_degrees(src, dst, n, n)
    k = split_k or select_split_k(deg, waste_budget, deg_multiple)
    nbr, res_s, res_d = ell_from_edges(src, dst, n, n, k)
    cap = _round_up(max(res_s.size, 1), res_multiple)
    pad = cap - res_s.size
    res_s = np.concatenate([res_s, np.full(pad, n, np.int32)])
    res_d = np.concatenate([res_d, np.full(pad, n, np.int32)])
    return nbr, res_s, res_d, degree_offsets(np.where(deg > k, deg, 0)), k


def block_pad(g: CSRGraph, multiple: int) -> CSRGraph:
    """Pad vertex count to a multiple (replaces the paper's odd-rank residuum
    handling, §7.2.1 — static padding instead of special-case code paths)."""
    n_pad = -(-g.n // multiple) * multiple
    if n_pad == g.n:
        return g
    row_ptr = np.concatenate(
        [g.row_ptr, np.full(n_pad - g.n, g.row_ptr[-1], dtype=g.row_ptr.dtype)]
    )
    return CSRGraph(
        n=n_pad,
        row_ptr=row_ptr,
        col_idx=g.col_idx,
        src=g.src,
        dst=g.dst,
        m_input=g.m_input,
    )
