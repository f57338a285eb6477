"""ELL frontier-expansion SpMV: the XLA formulation on every backend.

One parameterized function — :func:`gspmm` (the DGL op x reduce shape) —
stands behind *every* entry point in this package: the min-parent BFS
semantics

    out[r] = min over d of ( nbr[r, d]  if frontier[nbr[r, d]] else INF )

is its ``message=None, reduce=None`` instantiation, the pull direction is
the same call with an unreached row mask, and the frontier-algebra value
expansions (SSSP min-plus, CC label copy, PageRank plus-times) pass their
own message/reduce closures.

This is the production path on TPU as well as the CPU one.  The frontier
probe is a data-dependent gather from the packed bitmap, and Mosaic does
not lower a vector gather from VMEM at arbitrary indices ("Cannot do int
indexing on TPU"), so the ELL slab expands through XLA's gather.

``nbr``: (n_rows, max_deg) int32 destination-major neighbor lists, padded
with ``n_cols`` (which always misses the frontier).  ``frontier``: bitmap
of n_cols bits packed into uint32 words (vertical width-1 layout of
kernels/bitpack).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INF = jnp.iinfo(jnp.int32).max


def ell_from_coo(src, dst, n_rows: int, n_cols: int, max_deg: int):
    """Host-free COO->ELL conversion (jnp; for tests and small blocks)."""
    order = jnp.argsort(dst)
    src_s, dst_s = src[order], dst[order]
    # position of each edge within its destination row
    ones = jnp.ones_like(dst_s)
    pos = jax.ops.segment_sum(ones, dst_s, num_segments=n_rows + 1)
    # recompute per-edge rank via cumsum trick
    idx = jnp.arange(dst_s.shape[0])
    row_start = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(pos)[:-1].astype(jnp.int32)])
    rank = idx - row_start[jnp.minimum(dst_s, n_rows)]
    nbr = jnp.full((n_rows + 1, max_deg), n_cols, jnp.int32)
    valid = (rank < max_deg) & (dst_s < n_rows)
    nbr = nbr.at[jnp.where(valid, dst_s, n_rows), jnp.where(valid, rank, 0)].set(
        jnp.where(valid, src_s, n_cols).astype(jnp.int32)
    )
    return nbr[:n_rows]


def frontier_bit(words: jax.Array, idx: jax.Array, n_cols: int) -> jax.Array:
    """Test membership bits for (possibly out-of-range) indices."""
    safe = jnp.minimum(idx, n_cols - 1)
    chunk, within = safe // 1024, safe % 1024
    w = words[chunk * 32 + within % 32]  # vertical b=1 layout: word j of chunk
    # vertical layout: value i at word (i % 32b=32) shift (i // 32): see bitpack
    shift = within // 32
    bit = (w >> shift) & jnp.uint32(1)
    return (bit == 1) & (idx < n_cols)


def gspmm(
    nbr: jax.Array,
    f_words: jax.Array,
    n_cols: int,
    *,
    message=None,
    reduce=None,
    empty=INF,
    u_words: jax.Array | None = None,
) -> jax.Array:
    """One op x reduce reference behind every ELL expansion entry point.

        out[r] = reduce over d of message(r, nbr[r, d])  where the slot's
                 source is in the frontier   (``empty`` if none hit)

    ``message(rows, cols)`` maps the (n_rows, max_deg) destination/source
    id grids to per-slot candidate values; ``None`` is the min-parent copy
    op (the candidate IS the source id).  ``reduce(vals, axis)`` defaults
    to ``jnp.min``; sum-algebras pass a decode-add-encode closure whose
    identity is their ``empty`` sentinel, so no extra masking is needed.
    ``u_words``, if given, is the packed unreached-row bitmap of the pull
    direction: finished destination rows collapse to ``empty``.
    """
    n_rows = nbr.shape[0]
    hit = frontier_bit(f_words, nbr, n_cols)
    if message is None:
        vals = nbr
    else:
        rows = jax.lax.broadcasted_iota(jnp.int32, nbr.shape, 0)
        vals = message(rows, nbr)
    cand = jnp.where(hit, vals, empty)
    out = (jnp.min if reduce is None else reduce)(cand, axis=1)
    if u_words is not None:
        unreached = frontier_bit(
            u_words, jnp.arange(n_rows, dtype=jnp.int32), n_rows
        )
        out = jnp.where(unreached, out, empty)
    return out


def spmv_min(nbr: jax.Array, f_words: jax.Array, n_cols: int) -> jax.Array:
    """out (n_rows,) int32 = min frontier neighbor id per row (INF if none)."""
    return gspmm(nbr, f_words, n_cols)


def spmv_min_planes(nbr: jax.Array, f_words: jax.Array, n_cols: int) -> jax.Array:
    """Multi-source push expansion: ``f_words`` is ``(B, n_cols/32)`` packed
    frontier planes -> ``(B, n_rows)`` per-plane min frontier neighbors."""
    return jax.lax.map(lambda fw: spmv_min(nbr, fw, n_cols), f_words)


def spmv_pull_min_planes(
    nbr: jax.Array, f_words: jax.Array, u_words: jax.Array, n_cols: int
) -> jax.Array:
    """Multi-source pull expansion: ``(B, n_cols/32)`` frontier planes and
    ``(B, n_rows/32)`` unreached planes -> ``(B, n_rows)`` per-plane mins."""
    return jax.lax.map(
        lambda a: spmv_pull_min(nbr, a[0], a[1], n_cols), (f_words, u_words)
    )


def spmv_pull_min(
    nbr: jax.Array, f_words: jax.Array, u_words: jax.Array, n_cols: int
) -> jax.Array:
    """Pull (bottom-up) expansion: only *unreached* rows probe their
    neighbor lists against the frontier bitmap.

    ``u_words``: vertical b=1 bitmap of n_rows bits — bit set when the row
    vertex is still unreached.  Rows with a clear bit produce INF (they
    neither need a parent nor should pay for the probe on hardware).
    """
    return gspmm(nbr, f_words, n_cols, u_words=u_words)


def gspmm_planes(
    nbr: jax.Array,
    f_words: jax.Array,
    x: jax.Array,
    n_cols: int,
    alg,
    *,
    row_base=0,
    col_base=0,
    u_words: jax.Array | None = None,
) -> jax.Array:
    """op x reduce ELL value expansion over (B,) frontier/value planes.

    Each frontier hit proposes ``alg.edge_message(x[src], src + col_base,
    dst + row_base)``; candidates combine per destination row under the
    algebra's reduce (min, or a decode-add-encode sum).  ``u_words``, if
    given, masks finished destination rows to the algebra's empty sentinel
    (pull direction).
    """
    n_x = x.shape[1]
    if alg.reduce == "min":
        reduce = None
    else:
        reduce = lambda vals, axis: alg.enc(jnp.sum(alg.dec(vals), axis=axis))  # noqa: E731

    def one(fw, xp, uw):
        def message(rows, cols):
            xs = xp[jnp.minimum(cols, n_x - 1)]
            return alg.edge_message(xs, cols + col_base, rows + row_base)

        return gspmm(
            nbr, fw, n_cols, message=message, reduce=reduce,
            empty=alg.empty, u_words=uw,
        )

    if u_words is None:
        return jax.lax.map(lambda a: one(*a, None), (f_words, x))
    return jax.lax.map(lambda a: one(*a), (f_words, x, u_words))
