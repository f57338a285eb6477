"""Local-expansion backends: block storage as a first-class registry axis.

The communication plane made the *wire* pluggable (wire plans) and the
*direction* pluggable (traversal policies); this module does the same for
the third leg of a distributed BFS level — the **local expansion** that
turns the gathered frontier slice into per-destination candidate parents.
Data-structure choice dominates on-node BFS cost once communication is
optimized (Buluc & Madduri, arXiv:1104.4518), and the winning structure is
degree-dependent: dense ELL neighbor slabs stream through the SpMV
gather, but hub rows make a slab-wide ELL unaffordable, so hubs want to
stay COO (Bisson et al., arXiv:1408.1605).  Three backends, resolved by
name through :func:`repro.comm.registry.expansion`:

* ``coo``    — the sentinel-padded, destination-major edge arrays; each
  row's minimum frontier source is its first hit, found by one running
  minimum over the edges (no scatter).
* ``ell``    — dense ``(rows, k)`` neighbor blocks driven through
  :mod:`repro.kernels.spmv` push/pull (``k`` covers the heaviest row).
* ``hybrid`` — per-block degree split: rows with degree <= ``k`` live in
  an ELL slab, the hub residue stays COO; the ``auto`` split picks ``k``
  from the block's degree histogram so ELL padding waste stays under a
  budget (:func:`repro.graphgen.builder.select_split_k`).  ``hybrid`` is
  also reachable under the alias ``auto``.

Every backend produces **bit-identical** candidates — each row's edge set
lives in exactly one structure, and the min-parent semiring commutes with
the split — and expansion is compute-local: no backend touches a
collective, so CommStats and the lowered HLO are invariant under backend
choice (asserted by tests/test_expansion.py).

The containers are built at partition time (:func:`repro.core.csr.ell_blocked`
/ :func:`repro.core.csr.hybrid_blocked`) with static, sentinel-padded
shapes and are sharded alongside the COO edge arrays by
:func:`repro.core.distributed_bfs.shard_blocked`.
"""

from __future__ import annotations

import weakref
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.comm import registry as wire_registry
from repro.comm.formats import INF
from repro.core import csr as csrmod
from repro.graphgen import builder
from repro.kernels.bitpack import ops as bp_ops
from repro.kernels.spmv import ref as spmv_ref

#: name aliases accepted by :func:`resolve` (the example's ``--expand auto``)
ALIASES = {"auto": "hybrid"}


def resolve(name: str):
    """Resolve an expansion backend by name through the unified registry."""
    return wire_registry.expansion(ALIASES.get(name, name))


BACKENDS = ("coo", "ell", "hybrid")


def _chunk_pad(m: int) -> int:
    return m + (-m) % 1024


def _pack_planes(bits: jax.Array) -> jax.Array:
    """(B, m) bool membership planes -> (B, chunk_pad(m)/32) packed words
    (the vertical width-1 layout every bitmap probe in the repo uses)."""
    b, m = bits.shape
    pad = (-m) % 1024
    if pad:
        bits = jnp.concatenate([bits, jnp.zeros((b, pad), bits.dtype)], axis=1)
    return bp_ops.pack_planes(bits.astype(jnp.uint32), 1)


class LocalBlock(NamedTuple):
    """One rank's expansion-ready storage (built inside ``shard_map``).

    ``src``/``dst`` hold COO edges — the whole block for the ``coo``
    backend, the hub residue for ``hybrid``, unused (but carried for the
    degree vector) for ``ell``; ``nbr`` is the dense ELL slab or ``None``.
    Sentinels follow the partition convention: ``n_cols`` on the source
    side, ``n_rows`` on the destination side.  COO edges are
    destination-major (sorted by destination, then source; the partition
    and the single-device driver lay them out so), and ``row_ptr`` holds
    each destination row's first edge in them.  It is never searched for
    on the distributed path: the whole block's offsets are built with the
    partition and the residue's with the hybrid containers, on the host at
    set-up, and placed next to the edge blocks.  The single-device driver
    sorts its edges on the device and searches their offsets there.
    """

    src: jax.Array  # (e,) column-local sources
    dst: jax.Array  # (e,) row-local destinations
    nbr: jax.Array | None  # (n_rows, k) ELL slab, sentinel n_cols
    n_rows: int
    n_cols: int
    row_ptr: jax.Array | None = None  # (n_rows + 1,) COO row offsets


def dst_major(src, dst):
    """Sort an edge list destination-major (by destination, then source),
    the layout the COO expansion reads."""
    dst, src = jax.lax.sort((dst, src), num_keys=2)
    return src, dst


#: positions are int32: a block past 2**31 - 1 edge slots still lowers (the
#: dry-run cells), with positions clamped, but cannot run
_POS_MAX = int(jnp.iinfo(jnp.int32).max)


def row_offsets(dst, n_rows: int) -> jax.Array:
    """(n_rows + 1,) start of each destination row in a destination-major
    edge array (sentinel destinations ``>= n_rows`` sort past the last):
    a binary search per row, all rows at once.  For the single-device
    driver, whose edges are sorted on the device; the distributed driver
    reads offsets built at set-up (:func:`repro.graphgen.builder.degree_offsets`)."""
    e = min(dst.shape[0], _POS_MAX)
    rows = jax.lax.iota(dst.dtype, n_rows + 1)

    def halve(_, bounds):
        lo, hi = bounds
        mid = lo + (hi - lo) // 2
        right = (lo < hi) & (jnp.take(dst, mid, mode="clip") < rows)
        return (jnp.where(right, mid + 1, lo),
                jnp.where(right | (lo >= hi), hi, mid))

    lo = jnp.zeros((n_rows + 1,), jnp.int32)
    hi = jnp.full((n_rows + 1,), e, jnp.int32)
    lo, _ = jax.lax.fori_loop(0, e.bit_length(), halve, (lo, hi))
    return lo


def _first_hit(src, row_ptr, hit):
    """Per destination row, the smallest source whose edge ``hit``s.

    Edges are destination-major with ascending sources inside each row, so
    the row's minimum hit is its FIRST hit: one reverse running minimum of
    hit positions finds it for every row at once — a streaming pass over
    the edges instead of a scatter into the rows."""
    e = min(src.shape[0], _POS_MAX)
    pos = jnp.where(hit, jax.lax.iota(jnp.int32, src.shape[0]), e)
    nxt = jax.lax.cummin(pos, reverse=True)  # first hit at or after slot
    first = jnp.take(nxt, row_ptr[:-1], mode="clip")
    found = (row_ptr[:-1] < row_ptr[1:]) & (first < row_ptr[1:])
    return jnp.where(found, jnp.take(src, first, mode="clip"), INF)


@obs.scoped("residue")
def _coo_push(src, row_ptr, n_cols: int, f):
    """(B, n_cols) frontier planes -> (B, n_rows) min frontier source per
    destination (column-LOCAL ids) over destination-major edges."""

    def one(fp):
        hit = fp[jnp.clip(src, 0, n_cols - 1)] & (src < n_cols)
        return _first_hit(src, row_ptr, hit)

    return jax.lax.map(one, f)


@obs.scoped("residue")
def _coo_pull(src, row_ptr, n_cols: int, f, unreached):
    """Pull over COO edges: the frontier is probed through its *packed*
    bitmap (the representation switch the pull direction is about), and
    only unreached destinations take a candidate."""
    n_cp = _chunk_pad(n_cols)
    words = _pack_planes(f)

    def one(wp, un):
        hit = spmv_ref.frontier_bit(wp, src, n_cp) & (src < n_cols)
        return jnp.where(un, _first_hit(src, row_ptr, hit), INF)

    return jax.lax.map(lambda a: one(*a), (words, unreached))


@obs.scoped("slab")
def _ell_push(nbr, n_cols: int, f):
    """ELL slab push through the plane-batched SpMV."""
    return spmv_ref.spmv_min_planes(nbr, _pack_planes(f), _chunk_pad(n_cols))


@obs.scoped("slab")
def _ell_pull(nbr, n_cols: int, f, unreached):
    """ELL pull: resident frontier + unreached bitmaps, finished rows INF."""
    return spmv_ref.spmv_pull_min_planes(
        nbr, _pack_planes(f), _pack_planes(unreached), _chunk_pad(n_cols)
    )


@obs.scoped("residue")
def _coo_push_value(src, dst, n_rows, n_cols, f, x, alg, row_base, col_base):
    """Value-algebra push over COO edges: each active edge proposes
    ``alg.edge_message`` of its source's value, reduced per destination
    with the algebra's combine (column-LOCAL frontier, GLOBAL payload)."""

    def one(fp, xp):
        s_cl = jnp.clip(src, 0, n_cols - 1)
        act = fp[s_cl] & (src < n_cols)
        msg = alg.edge_message(xp[s_cl], src + col_base, dst + row_base)
        cand = jnp.where(act, msg, alg.empty)
        return alg.segment_combine(cand, dst, n_rows + 1)[:n_rows]

    return jax.lax.map(lambda a: one(*a), (f, x))


@obs.scoped("residue")
def _coo_pull_value(src, dst, n_rows, n_cols, f, unreached, x, alg,
                    row_base, col_base):
    """Value-algebra pull over COO edges: the frontier is probed through
    its packed bitmap and only ``unreached``-masked destinations (the
    algebra's pull mask) accumulate candidates."""
    n_cp = _chunk_pad(n_cols)
    words = _pack_planes(f)

    def one(wp, un, xp):
        s_cl = jnp.clip(src, 0, n_cols - 1)
        hit = spmv_ref.frontier_bit(wp, src, n_cp) & (src < n_cols)
        pull = un[jnp.clip(dst, 0, n_rows - 1)] & (dst < n_rows)
        msg = alg.edge_message(xp[s_cl], src + col_base, dst + row_base)
        cand = jnp.where(hit & pull, msg, alg.empty)
        return alg.segment_combine(cand, dst, n_rows + 1)[:n_rows]

    return jax.lax.map(lambda a: one(*a), (words, unreached, x))


@obs.scoped("slab")
def _ell_push_value(nbr, n_cols: int, f, x, alg, row_base, col_base):
    """ELL value push through the op x reduce gspmm."""
    return spmv_ref.gspmm_planes(
        nbr, _pack_planes(f), x, _chunk_pad(n_cols), alg,
        row_base=row_base, col_base=col_base,
    )


@obs.scoped("slab")
def _ell_pull_value(nbr, n_cols: int, f, unreached, x, alg, row_base, col_base):
    """ELL value pull: masked destinations collapse to the empty sentinel."""
    return spmv_ref.gspmm_planes(
        nbr, _pack_planes(f), x, _chunk_pad(n_cols), alg,
        row_base=row_base, col_base=col_base, u_words=_pack_planes(unreached),
    )


class ExpansionBackend:
    """One local-expansion data structure (or a degree split over two).

    Host side, ``graph_arrays``/``block_arrays`` build the backend's extra
    device arrays — ``()`` for COO — from the flat edge list / the 2D
    :class:`~repro.core.csr.BlockedGraph`; each distributed array leads
    with the ``(R, C)`` grid axes so the driver can shard it like the edge
    blocks.  Device side, ``local_block`` assembles the per-rank
    :class:`LocalBlock` from the edges, their row offsets ``row_ptr`` and
    the extra arrays, and ``push_planes``/``pull_planes`` expand all B
    frontier planes at once, returning ``(B, n_rows)`` column-local
    min-candidate ids (INF where none) — the traversal policy owns
    globalization and the wire.
    """

    name: str = ""
    #: trailing (per-rank) rank of each distributed extra array, after the
    #: leading (R, C) grid axes — lets the driver build shard specs without
    #: materializing the containers
    extra_ndims: tuple[int, ...] = ()

    def graph_arrays(self, src, dst, n: int) -> tuple[np.ndarray, ...]:
        return ()

    def block_arrays(self, bg: csrmod.BlockedGraph) -> tuple[np.ndarray, ...]:
        return ()

    def local_block(self, src, dst, row_ptr, extra, n_rows: int,
                    n_cols: int) -> LocalBlock:
        raise NotImplementedError

    def push_planes(self, blk: LocalBlock, f):
        raise NotImplementedError

    def pull_planes(self, blk: LocalBlock, f, unreached):
        raise NotImplementedError

    def push_value_planes(self, blk: LocalBlock, f, x, alg, *, row_base=0,
                          col_base=0):
        """Value-algebra push: (B, n_cols) frontier + value planes ->
        (B, n_rows) combined candidate values (``alg.empty`` where none).
        ``row_base``/``col_base`` globalize the block-local ids for the
        algebra's edge messages."""
        raise NotImplementedError

    def pull_value_planes(self, blk: LocalBlock, f, unreached, x, alg, *,
                          row_base=0, col_base=0):
        raise NotImplementedError

    def describe(self, bg: csrmod.BlockedGraph) -> list[dict]:
        """Per-block split/padding report (the example's --expand print)."""
        return []


class CooExpansion(ExpansionBackend):
    name = "coo"

    def local_block(self, src, dst, row_ptr, extra, n_rows, n_cols):
        assert extra == (), extra
        return LocalBlock(src=src, dst=dst, nbr=None, n_rows=n_rows,
                          n_cols=n_cols, row_ptr=row_ptr)

    def push_planes(self, blk, f):
        return _coo_push(blk.src, blk.row_ptr, blk.n_cols, f)

    def pull_planes(self, blk, f, unreached):
        return _coo_pull(blk.src, blk.row_ptr, blk.n_cols, f, unreached)

    def push_value_planes(self, blk, f, x, alg, *, row_base=0, col_base=0):
        return _coo_push_value(
            blk.src, blk.dst, blk.n_rows, blk.n_cols, f, x, alg,
            row_base, col_base,
        )

    def pull_value_planes(self, blk, f, unreached, x, alg, *, row_base=0,
                          col_base=0):
        return _coo_pull_value(
            blk.src, blk.dst, blk.n_rows, blk.n_cols, f, unreached, x, alg,
            row_base, col_base,
        )


class EllExpansion(ExpansionBackend):
    name = "ell"
    extra_ndims = (2,)  # (n_r, k) slab

    def graph_arrays(self, src, dst, n):
        nbr, _ = builder.ell_graph_arrays(np.asarray(src), np.asarray(dst), n)
        return (nbr,)

    def block_arrays(self, bg):
        return (self._blocks(bg).nbr,)

    def _blocks(self, bg):
        return _graph_cached(self, bg, csrmod.ell_blocked)

    def local_block(self, src, dst, row_ptr, extra, n_rows, n_cols):
        (nbr,) = extra
        return LocalBlock(src=src, dst=dst, nbr=nbr, n_rows=n_rows, n_cols=n_cols)

    def push_planes(self, blk, f):
        return _ell_push(blk.nbr, blk.n_cols, f)

    def pull_planes(self, blk, f, unreached):
        return _ell_pull(blk.nbr, blk.n_cols, f, unreached)

    def push_value_planes(self, blk, f, x, alg, *, row_base=0, col_base=0):
        return _ell_push_value(
            blk.nbr, blk.n_cols, f, x, alg, row_base, col_base
        )

    def pull_value_planes(self, blk, f, unreached, x, alg, *, row_base=0,
                          col_base=0):
        return _ell_pull_value(
            blk.nbr, blk.n_cols, f, unreached, x, alg, row_base, col_base
        )

    def describe(self, bg):
        blocks = self._blocks(bg)
        waste = blocks.padding_ratio()
        return [
            {"block": (i, j), "split_k": int(blocks.split_k[i, j]),
             "padding_ratio": float(waste[i, j])}
            for i in range(bg.part.rows) for j in range(bg.part.cols)
        ]


class HybridExpansion(ExpansionBackend):
    """Degree-split COO/ELL: low-degree rows on the slab, hubs in COO."""

    name = "hybrid"
    #: (n_r, k) slab, (r_cap,) residue src/dst, (n_r + 1,) residue offsets
    extra_ndims = (2, 1, 1, 1)

    def __init__(self, waste_budget: float = 0.5, split_k: int | None = None):
        self.waste_budget = waste_budget
        self.split_k = split_k

    def graph_arrays(self, src, dst, n):
        nbr, res_s, res_d, res_ptr, _ = builder.hybrid_graph_arrays(
            np.asarray(src), np.asarray(dst), n,
            waste_budget=self.waste_budget, split_k=self.split_k,
        )
        return (nbr, res_s, res_d, res_ptr)

    def _blocks(self, bg):
        return _graph_cached(
            self, bg,
            lambda b: csrmod.hybrid_blocked(
                b, waste_budget=self.waste_budget, split_k=self.split_k
            ),
        )

    def block_arrays(self, bg):
        h = self._blocks(bg)
        return (h.nbr, h.res_src, h.res_dst, h.res_row_ptr)

    def local_block(self, src, dst, row_ptr, extra, n_rows, n_cols):
        nbr, res_src, res_dst, res_ptr = extra
        return LocalBlock(
            src=res_src, dst=res_dst, nbr=nbr, n_rows=n_rows, n_cols=n_cols,
            row_ptr=res_ptr,
        )

    def push_planes(self, blk, f):
        return jnp.minimum(
            _ell_push(blk.nbr, blk.n_cols, f),
            _coo_push(blk.src, blk.row_ptr, blk.n_cols, f),
        )

    def pull_planes(self, blk, f, unreached):
        return jnp.minimum(
            _ell_pull(blk.nbr, blk.n_cols, f, unreached),
            _coo_pull(blk.src, blk.row_ptr, blk.n_cols, f, unreached),
        )

    def push_value_planes(self, blk, f, x, alg, *, row_base=0, col_base=0):
        # each row's edge set lives in exactly one structure, so the
        # algebra's combine (min OR sum) merges the two halves exactly
        return alg.combine(
            _ell_push_value(blk.nbr, blk.n_cols, f, x, alg, row_base, col_base),
            _coo_push_value(
                blk.src, blk.dst, blk.n_rows, blk.n_cols, f, x, alg,
                row_base, col_base,
            ),
        )

    def pull_value_planes(self, blk, f, unreached, x, alg, *, row_base=0,
                          col_base=0):
        return alg.combine(
            _ell_pull_value(
                blk.nbr, blk.n_cols, f, unreached, x, alg, row_base, col_base
            ),
            _coo_pull_value(
                blk.src, blk.dst, blk.n_rows, blk.n_cols, f, unreached, x, alg,
                row_base, col_base,
            ),
        )

    def describe(self, bg):
        h = self._blocks(bg)
        waste = h.padding_ratio()
        return [
            {"block": (i, j), "split_k": int(h.split_k[i, j]),
             "padding_ratio": float(waste[i, j]),
             "residue_edges": int((h.res_src[i, j] < bg.part.n_c).sum())}
            for i in range(bg.part.rows) for j in range(bg.part.cols)
        ]


def _graph_cached(backend, bg, build):
    """One-entry per-backend container cache keyed on graph identity.

    Callers rebuild the same ``BlockedGraph``'s containers repeatedly (one
    ``shard_blocked`` per wire mode in the example, plus ``describe``);
    the O(m) host-side build only needs to run once.  Identity is checked
    through a weakref so a recycled ``id`` after garbage collection cannot
    alias a different graph.
    """
    cached = getattr(backend, "_graph_cache", None)
    if cached is not None and cached[0]() is bg:
        return cached[1]
    blocks = build(bg)
    backend._graph_cache = (weakref.ref(bg), blocks)
    return blocks


for _b in (CooExpansion(), EllExpansion(), HybridExpansion()):
    wire_registry.register_expansion(_b)
del _b
