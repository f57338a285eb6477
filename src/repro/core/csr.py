"""Device-side graph containers and the 2D block partitioner (paper §2.6.2).

The 2D partition: an R x C processor grid; rank (i, j) owns adjacency block
``A_ij`` = edges (u -> v) with ``u`` in *column slice* j (width n/C) and
``v`` in *row slice* i (width n/R).  Vertex *ownership* (who stores
parent[v] / the frontier bit of v) follows the row-phase output layout:
the global vertex space is split into R*C chunks of size ``s = n/(R*C)``;
rank (i, j) owns chunk ``q = i*C + j``.

Static shapes: every per-rank edge block is padded to the same capacity
``e_cap`` with sentinel edges (src = n_c, dst = s_rows) that fall out of all
gathers/segment reductions — the TPU-native replacement for the paper's
"residuum" special cases (§7.2.1).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro import obs
from repro.graphgen.builder import (
    CSRGraph,
    _round_up,
    degree_offsets,
    ell_from_edges,
    select_split_k,
)


@dataclasses.dataclass(frozen=True)
class Partition2D:
    """Geometry of the R x C grid over n (padded) vertices."""

    n: int  # padded global vertex count
    n_orig: int  # pre-padding vertex count
    rows: int  # R
    cols: int  # C

    @property
    def n_r(self) -> int:  # row-slice width (vertices per grid row)
        return self.n // self.rows

    @property
    def n_c(self) -> int:  # column-slice width
        return self.n // self.cols

    @property
    def chunk(self) -> int:  # owned-chunk width s
        return self.n // (self.rows * self.cols)

    def owner(self, v: np.ndarray) -> np.ndarray:
        """Owned-chunk index q = v // s; rank (q // C, q % C)."""
        return v // self.chunk

    def transpose_perm(self) -> list[tuple[int, int]]:
        """ppermute pairs implementing the paper's TransposeVector (Alg. 2 l.4).

        Rank p = i*C + j owns chunk q = p.  The column phase needs rank
        (i, j) to hold chunk j*R + i (so the column-j all-gather assembles
        the contiguous column slice).  Returns (src_rank, dst_rank) pairs
        over the row-major linearized grid.
        """
        pairs = []
        r, c = self.rows, self.cols
        for i in range(r):
            for j in range(c):
                src = i * c + j  # owns chunk q = src
                q = src
                # chunk q is needed (in column phase) by rank (i', j') with
                # j'*R + i' = q  =>  j' = q // R, i' = q % R
                jp, ip = q // r, q % r
                dst = ip * c + jp
                pairs.append((src, dst))
        return pairs


@dataclasses.dataclass(frozen=True)
class BlockedGraph:
    """2D-blocked edge arrays, shaped (R, C, e_cap) with local indices.

    ``src_local`` indexes into the column slice [0, n_c); ``dst_local`` into
    the row slice [0, n_r).  Each block is destination-major (sorted by
    destination, then source), and padding edges at the end use (n_c, n_r)
    sentinels.  ``row_ptr[i, j, v]`` is the first edge of row ``v`` in
    block ``A_ij``; the sentinel tail sorts past the last row, so
    ``row_ptr[i, j, n_r]`` is the block's valid edge count.
    """

    part: Partition2D
    src_local: np.ndarray  # (R, C, e_cap) int32
    dst_local: np.ndarray  # (R, C, e_cap) int32
    row_ptr: np.ndarray  # (R, C, n_r + 1) int32 row offsets per block
    e_counts: np.ndarray  # (R, C) int64 true edge counts per block
    m_input: int

    @property
    def e_cap(self) -> int:
        return int(self.src_local.shape[-1])

    def degrees(self) -> np.ndarray:
        """(R, C, n_r) per-destination degree of every block."""
        return np.diff(self.row_ptr, axis=-1)


@dataclasses.dataclass(frozen=True)
class ELLBlocks:
    """Dense destination-major neighbor slabs, one per 2D block.

    ``nbr[i, j]`` is the ``(n_r, k)`` ELL slab of block ``A_ij``: row ``v``
    (row-local destination) lists its column-local frontier-side neighbors,
    sentinel-padded with ``n_c`` (a vertex id that never hits a frontier
    bitmap).  Shapes are static across blocks — the slab width is the max
    over blocks, rounded to a multiple of 8 — so the arrays
    shard alongside the COO edge arrays.
    """

    part: Partition2D
    nbr: np.ndarray  # (R, C, n_r, k) int32, sentinel n_c
    split_k: np.ndarray  # (R, C) int32 per-block degree split

    @property
    def k(self) -> int:
        return int(self.nbr.shape[-1])

    def padding_ratio(self) -> np.ndarray:
        """(R, C) fraction of slab slots holding sentinels (ELL waste)."""
        slots = self.nbr.shape[-2] * self.nbr.shape[-1]
        pad = (self.nbr == self.part.n_c).sum(axis=(-2, -1))
        return pad / slots


@dataclasses.dataclass(frozen=True)
class HybridBlocks:
    """Per-block degree-split COO/ELL storage (Bisson et al.'s hub split).

    Rows with degree <= the block's ``split_k`` live in the shared-width
    ELL slab; the hub residue keeps its edges in sentinel-padded COO arrays
    of one static capacity, so every array shards like the flat edge
    blocks.  ``min(slab expansion, residue expansion)`` is bit-identical to
    the flat segment_min because each row's edges live in exactly one of
    the two structures.  ``res_row_ptr`` holds the residue's row offsets:
    rows on the slab are empty there.
    """

    part: Partition2D
    nbr: np.ndarray  # (R, C, n_r, k) int32, sentinel n_c
    res_src: np.ndarray  # (R, C, r_cap) int32, sentinel n_c
    res_dst: np.ndarray  # (R, C, r_cap) int32, sentinel n_r
    res_row_ptr: np.ndarray  # (R, C, n_r + 1) int32 residue row offsets
    split_k: np.ndarray  # (R, C) int32 per-block degree split

    @property
    def k(self) -> int:
        return int(self.nbr.shape[-1])

    @property
    def r_cap(self) -> int:
        return int(self.res_src.shape[-1])

    def padding_ratio(self) -> np.ndarray:
        """(R, C) fraction of slab slots holding sentinels (ELL waste)."""
        slots = self.nbr.shape[-2] * self.nbr.shape[-1]
        pad = (self.nbr == self.part.n_c).sum(axis=(-2, -1))
        return pad / slots


def ell_slab_width(bg: BlockedGraph, deg_multiple: int = 8) -> int:
    """The slab width :func:`ell_blocked` will use: the max row degree over
    ALL blocks, rounded to a multiple of 8.  The single place the
    pure-ELL affordability estimate lives, so memory guards (the benchmark's
    slab budget) cannot drift from what the container actually allocates."""
    max_deg = int(bg.degrees().max(initial=0))
    return _round_up(max(max_deg, 1), deg_multiple)


def ell_blocked(bg: BlockedGraph, deg_multiple: int = 8) -> ELLBlocks:
    """Pure-ELL containers: one slab width covering every block's heaviest
    row — affordable only when the degree distribution is flat; hub-heavy
    blocks want :func:`hybrid_blocked`."""
    part = bg.part
    r, c = part.rows, part.cols
    k = ell_slab_width(bg, deg_multiple)
    nbr = np.empty((r, c, part.n_r, k), np.int32)
    for i in range(r):
        for j in range(c):
            slab, res_s, _ = ell_from_edges(
                bg.src_local[i, j], bg.dst_local[i, j], part.n_r, part.n_c, k
            )
            assert res_s.size == 0, "pure ELL must cover every row"
            nbr[i, j] = slab
    return ELLBlocks(part=part, nbr=nbr, split_k=np.full((r, c), k, np.int32))


@obs.span("hybrid_blocked")
def hybrid_blocked(
    bg: BlockedGraph,
    waste_budget: float = 0.5,
    split_k: int | None = None,
    deg_multiple: int = 8,
    res_multiple: int = 1024,
) -> HybridBlocks:
    """Per-block degree-split containers built at partition time.

    Each block's split ``k`` comes from its own degree histogram
    (:func:`repro.graphgen.builder.select_split_k`, keeping ELL padding
    waste under ``waste_budget``) unless a fixed ``split_k`` is forced; the
    slab width and residue capacity are the max over blocks so shapes stay
    static for ``shard_map``.  The residue's row offsets come from the
    blocks' degree histograms, with the rows on the slab left empty.
    """
    part = bg.part
    r, c = part.rows, part.cols
    deg = bg.degrees()
    ks = np.empty((r, c), np.int32)
    for i in range(r):
        for j in range(c):
            ks[i, j] = split_k or select_split_k(deg[i, j], waste_budget, deg_multiple)
    width = _round_up(int(ks.max(initial=1)), deg_multiple)
    slabs = np.empty((r, c, part.n_r, width), np.int32)
    residues = []
    for i in range(r):
        for j in range(c):
            slab, res_s, res_d = ell_from_edges(
                bg.src_local[i, j], bg.dst_local[i, j], part.n_r, part.n_c,
                int(ks[i, j]), width=width,
            )
            slabs[i, j] = slab
            residues.append((res_s, res_d))
    r_cap = _round_up(max(max(s.size for s, _ in residues), 1), res_multiple)
    res_src = np.full((r, c, r_cap), part.n_c, np.int32)
    res_dst = np.full((r, c, r_cap), part.n_r, np.int32)
    for b, (res_s, res_d) in enumerate(residues):
        i, j = divmod(b, c)
        res_src[i, j, : res_s.size] = res_s
        res_dst[i, j, : res_d.size] = res_d
    res_deg = np.where(deg > ks[:, :, None], deg, 0)
    return HybridBlocks(
        part=part, nbr=slabs, res_src=res_src, res_dst=res_dst,
        res_row_ptr=degree_offsets(res_deg), split_k=ks,
    )


def padded_geometry(n: int, rows: int, cols: int,
                    chunk_multiple: int = 1024) -> tuple[int, int]:
    """(padded n, chunk width s) that :func:`partition_2d` will produce for
    an ``n``-vertex graph — the single place the padding rule lives, so
    artifact writers (BENCH_comm.json's byte-model geometry) cannot drift
    from the replay's actual partition."""
    n_pad = _round_up(max(n, rows * cols), rows * cols * chunk_multiple)
    return n_pad, n_pad // (rows * cols)


def partition_2d(
    g: CSRGraph,
    rows: int,
    cols: int,
    chunk_multiple: int = 1024,
    e_cap_multiple: int = 1024,
) -> BlockedGraph:
    """Partition a CSR graph onto an R x C grid with static-capacity blocks.

    ``chunk_multiple`` keeps the owned-chunk width s a multiple of the
    bit-packing chunk (1024) so compressed exchanges stay lane-aligned.
    Each block's row offsets are built here, from one degree histogram
    per block, so the device program never searches for them.
    """
    n, _ = padded_geometry(g.n, rows, cols, chunk_multiple)
    part = Partition2D(n=n, n_orig=g.n, rows=rows, cols=cols)
    # the symmetric CSR read transposed lists its edges destination-major
    # (by destination, then source): the layout the COO expansion reads.
    # The stable sort by block keeps that order inside every block.
    src, dst = g.dst, g.src

    bj = src // part.n_c  # block column of each edge
    bi = dst // part.n_r  # block row
    block = (bi * cols + bj).astype(np.min_scalar_type(rows * cols))
    counts = np.bincount(block, minlength=rows * cols)
    if rows * cols > 1:
        order = np.argsort(block, kind="stable")
        src, dst = src[order], dst[order]
    e_cap = _round_up(max(int(counts.max()), 1), e_cap_multiple)

    src_l = np.full((rows * cols, e_cap), part.n_c, dtype=np.int32)
    dst_l = np.full((rows * cols, e_cap), part.n_r, dtype=np.int32)
    deg = np.zeros((rows * cols, part.n_r), dtype=np.int64)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    for b in range(rows * cols):
        s0, cnt = starts[b], counts[b]
        if cnt == 0:
            continue
        i, j = divmod(b, cols)
        np.subtract(src[s0 : s0 + cnt], j * part.n_c, out=src_l[b, :cnt])
        np.subtract(dst[s0 : s0 + cnt], i * part.n_r, out=dst_l[b, :cnt])
        deg[b] = np.bincount(dst_l[b, :cnt], minlength=part.n_r)

    return BlockedGraph(
        part=part,
        src_local=src_l.reshape(rows, cols, e_cap),
        dst_local=dst_l.reshape(rows, cols, e_cap),
        row_ptr=degree_offsets(deg).reshape(rows, cols, part.n_r + 1),
        e_counts=counts.reshape(rows, cols),
        m_input=g.m_input,
    )
