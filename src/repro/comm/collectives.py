"""The adaptive collectives, rebuilt on the AdaptiveExchange engine.

Paper Alg. 4 on the accelerator: the column phase (ALLGATHERV + compress)
and the row phase (ALLTOALLV + compress) both dispatch through
:class:`repro.comm.engine.AdaptiveExchange`; the representation on the
wire is one of the :mod:`repro.comm.formats` chosen per communicator group
by the bucket ladder.  The bottom-up (pull) traversal direction swaps the
row id-stream ALLTOALLV for :func:`alltoall_bitmap_min` — a found-bitmap +
bit-packed-parent exchange whose cost is density-independent.  The
butterfly wire plan's staged rounds (:mod:`repro.comm.butterfly`) go
through :func:`ppermute_min_block` / :func:`ppermute_membership_block` —
one adaptive partner-exchange per stage, re-bucketed on the merged stream.
The int8 gradient all-reduce (beyond-paper) is the degenerate
single-format case of the same engine.

Every collective reports its bytes through :class:`repro.comm.stats.CommStats`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.comm.engine import AdaptiveExchange
from repro.comm.formats import (
    INF,
    BitmapFormat,
    BitmapParentFormat,
    DenseFormat,
    IdStreamFormat,
    Int8Format,
    RawIdFormat,
    pack_plane_meta,
    unpack_plane_meta,
)
from repro.comm.ladder import BucketLadder, stream_stats
from repro.comm.stats import CommStats
from repro.kernels.bitpack import ops as bp
from repro.kernels.quant import ref as quant


def _compact_planes(bits: jax.Array, cap: int, s: int) -> jax.Array:
    """(P, s) membership planes -> (P, cap) sorted ids, padded with ``s``."""
    return jax.vmap(lambda x: bp.compact_ids(x, cap, fill=s)[0])(bits)


def _scatter_membership(u_ids: jax.Array, s: int, group_size: int) -> jax.Array:
    """(group, cap) gathered ids -> (group*s,) membership vector."""
    offs = (jnp.arange(group_size, dtype=jnp.int32) * s)[:, None]
    flat = jnp.where(u_ids < s, u_ids + offs, group_size * s).reshape(-1)
    out = jnp.zeros((group_size * s + 1,), bool).at[flat].set(True)
    return out[: group_size * s]


# ---------------------------------------------------------------------------
# column phase: membership all-gather
# ---------------------------------------------------------------------------


def gather_bitmap(ex: AdaptiveExchange, bits: jax.Array) -> jax.Array:
    """Dense width-1 bitmap all-gather of an (s,) membership vector."""
    fmt = BitmapFormat(bits.shape[0])
    return fmt.unpack(ex.all_gather(fmt.pack(bits), fmt=fmt.name))


def gather_raw_ids(ex: AdaptiveExchange, bits: jax.Array) -> jax.Array:
    """Uncompressed 32-bit id-list all-gather (the paper's Baseline)."""
    s = bits.shape[0]
    fmt = RawIdFormat(s)
    ids, meta = fmt.pack(bits)
    g_ids = ex.all_gather(ids, fmt=fmt.name).reshape(ex.group_size, s)
    g_meta = ex.all_gather(meta, fmt=fmt.name, part="meta").reshape(ex.group_size, 1)
    u_ids, _ = jax.vmap(lambda i, m: fmt.unpack(i, m, fill=s))(g_ids, g_meta)
    return _scatter_membership(u_ids, s, ex.group_size)


def allgather_membership(
    bits: jax.Array,
    axis,
    ladder: BucketLadder,
    group_size: int,
    *,
    stats: CommStats | None = None,
    phase: str = "bfs/column",
):
    """Adaptive all-gather of a membership vector (paper's column phase).

    Every rank contributes an ``(s,)`` bool vector; returns the
    ``(group_size * s,)`` concatenation.  The transported representation is
    chosen per communicator group via the engine's consensus dispatch.
    """
    s = ladder.s
    ex = AdaptiveExchange(phase, axis, group_size, ladder, stats)
    if not ladder.specs:  # degenerate ladder: dense bitmap only
        return ex.dispatch(None, [lambda _: gather_bitmap(ex, bits)],
                           floor=BitmapFormat(s).name)
    count, exc_count = stream_stats(bits)

    def sparse_branch(fmt: IdStreamFormat):
        def run(_):
            ids, _ = bp.compact_ids(bits, fmt.spec.cap, fill=s)
            words, meta = fmt.pack(ids, count)
            g_words = ex.all_gather(words, fmt=fmt.name).reshape(
                group_size, fmt.data_words
            )
            g_meta = ex.all_gather(meta, fmt=fmt.name, part="meta").reshape(
                group_size, 2
            )
            u_ids, _, _ = jax.vmap(lambda w, m: fmt.unpack(w, m, fill=s))(
                g_words, g_meta
            )
            return _scatter_membership(u_ids, s, group_size)

        return run

    branches = [sparse_branch(f) for f in ladder.formats()] + [
        lambda _: gather_bitmap(ex, bits)
    ]
    return ex.dispatch(ladder.bucket_for(count, exc_count), branches,
                       floor=BitmapFormat(s).name)


# ---------------------------------------------------------------------------
# plane-batched column phase: B membership planes per exchange
# ---------------------------------------------------------------------------


def gather_bitmap_planes(ex: AdaptiveExchange, bits: jax.Array) -> jax.Array:
    """Width-1 bitmap all-gather of ``(B, s)`` membership planes ->
    ``(B, group_size * s)``."""
    b, s = bits.shape
    fmt = BitmapFormat(s)
    words = jax.vmap(fmt.pack)(bits)  # (B, s/32)
    g = ex.all_gather(words, fmt=fmt.name).reshape(ex.group_size, b, -1)
    mem = jax.vmap(jax.vmap(fmt.unpack))(g)  # (group, B, s)
    return jnp.moveaxis(mem, 0, 1).reshape(b, -1)


def gather_raw_ids_planes(ex: AdaptiveExchange, bits: jax.Array) -> jax.Array:
    """Uncompressed 32-bit id-list all-gather of ``(B, s)`` planes."""
    b, s = bits.shape
    fmt = RawIdFormat(s)
    ids, meta = jax.vmap(fmt.pack)(bits)  # (B, s), (B, 1)
    g_ids = ex.all_gather(ids, fmt=fmt.name).reshape(ex.group_size, b, s)
    g_meta = ex.all_gather(meta.reshape(b), fmt=fmt.name, part="meta").reshape(
        ex.group_size, b, 1
    )
    u_ids, _ = jax.vmap(jax.vmap(lambda i, m: fmt.unpack(i, m, fill=s)))(
        g_ids, g_meta
    )  # (group, B, s)
    return jax.vmap(
        lambda u: _scatter_membership(u, s, ex.group_size)
    )(jnp.moveaxis(u_ids, 0, 1))


def allgather_membership_planes(
    bits: jax.Array,
    axis,
    ladder: BucketLadder,
    group_size: int,
    *,
    stats: CommStats | None = None,
    phase: str = "bfs/column",
):
    """Adaptive all-gather of ``(B, s)`` membership planes (batched column
    phase) -> ``(B, group_size * s)``.

    One bucket consensus (max over every plane on every rank) and one pair
    of collectives serve all B planes; sparse stages pack each plane's id
    stream at the shared bucket and the B (count, exc) pairs ride a packed
    one-word-per-plane sideband (:func:`repro.comm.formats.pack_plane_meta`).
    """
    b, s = bits.shape
    assert s == ladder.s, (s, ladder.s)
    ex = AdaptiveExchange(phase, axis, group_size, ladder, stats, planes=b)
    if not ladder.specs:
        return ex.dispatch(None, [lambda _: gather_bitmap_planes(ex, bits)],
                           floor=BitmapFormat(s).name)
    counts, exc_counts = stream_stats(bits)
    my_bucket = jnp.max(jax.vmap(ladder.bucket_for)(counts, exc_counts))

    def sparse_branch(fmt: IdStreamFormat):
        def run(_):
            ids = _compact_planes(bits, fmt.spec.cap, s)
            words, meta = jax.vmap(fmt.pack)(ids, counts)  # (B, dw), (B, 2)
            pmeta = pack_plane_meta(meta[:, 0], meta[:, 1])  # (B,)
            g_words = ex.all_gather(words, fmt=fmt.name).reshape(
                group_size, b, fmt.data_words
            )
            g_meta = ex.all_gather(pmeta, fmt=fmt.name, part="meta").reshape(
                group_size, b
            )

            def unpack_one(w, m):
                c, e = unpack_plane_meta(m)
                u_ids, _, _ = fmt.unpack(w, jnp.stack([c, e]), fill=s)
                return u_ids

            u_ids = jax.vmap(jax.vmap(unpack_one))(g_words, g_meta)
            return jax.vmap(
                lambda u: _scatter_membership(u, s, group_size)
            )(jnp.moveaxis(u_ids, 0, 1))

        return run

    branches = [sparse_branch(f) for f in ladder.formats()] + [
        lambda _: gather_bitmap_planes(ex, bits)
    ]
    return ex.dispatch(my_bucket, branches, floor=BitmapFormat(s).name)


# ---------------------------------------------------------------------------
# column phase: value-plane all-gather (non-BFS frontier algebras)
# ---------------------------------------------------------------------------


def gather_values_planes(ex: AdaptiveExchange, x: jax.Array) -> jax.Array:
    """Dense int32 all-gather of ``(B, s)`` encoded value planes ->
    ``(B, group_size * s)``.

    The value companion of the membership gather: algebras whose message is
    not the source id itself (sssp distances, cc labels, pagerank mass)
    assemble the column slice of *source values* next to the membership
    bits.  Values travel as raw int32 words (width-32 packing is the
    identity), priced like a :class:`repro.comm.formats.DenseFormat` of
    ``s`` words per rank per plane.
    """
    b, s = x.shape
    g = ex.all_gather(x, fmt="values").reshape(ex.group_size, b, s)
    return jnp.moveaxis(g, 0, 1).reshape(b, -1)


# ---------------------------------------------------------------------------
# row phase: candidate all-to-all + min-reduce
# ---------------------------------------------------------------------------


def alltoall_dense_min(ex: AdaptiveExchange, prop: jax.Array) -> jax.Array:
    """Dense int32 all-to-all + min (raw/bitmap row phase and the fallback)."""
    c, s = prop.shape
    fmt = DenseFormat(s)
    recv = ex.all_to_all(prop, fmt=fmt.name).reshape(c, s)
    return jnp.min(recv, axis=0)


def alltoall_min_candidates(
    prop: jax.Array,
    axis,
    ladder: BucketLadder,
    group_size: int,
    *,
    stats: CommStats | None = None,
    phase: str = "bfs/row",
    n_c: int | None = None,
):
    """Adaptive all-to-all + min-reduce of candidate parents (row phase).

    ``prop``: (group_size, s) int32 — proposal subchunk per destination rank
    (INF = no candidate).  Returns (s,) int32 min over all senders of the
    subchunk addressed to this rank.  Ids are delta+patched-packed; parent
    payloads are bit-packed at the ladder's stored ``payload_width`` class
    and ride in the same wire words as the ids.

    ``n_c`` (the column-slice width) localizes the payload: the sender's
    candidates are global ids ``j * n_c + src_l``, but ``payload_width`` only
    covers the column-local offset — packing the global value would silently
    truncate its high bits whenever ``bit_length(n-1)`` exceeds the class
    that covers ``n_c``.  The sender therefore strips its own ``j * n_c``
    base before packing and the receiver re-adds it per received row (the
    all-to-all row index IS the sender's column), which is lossless at any
    grid width.
    """
    s = ladder.s
    c = group_size
    ex = AdaptiveExchange(phase, axis, group_size, ladder, stats)
    if not ladder.specs:
        return ex.dispatch(None, [lambda _: alltoall_dense_min(ex, prop)],
                           floor=DenseFormat(s).name)
    assert ladder.payload_width > 0, (
        "row-phase ladder must carry the parent payload: build it with "
        "BucketLadder.default(s, floor_words=s, payload_width=...)"
    )

    bits = prop < INF
    counts, exc_counts = stream_stats(bits)
    my_bucket = jnp.max(jax.vmap(ladder.bucket_for)(counts, exc_counts))
    base = 0 if n_c is None else jax.lax.axis_index(axis) * n_c

    def sparse_branch(fmt: IdStreamFormat):
        cap = fmt.spec.cap

        def run(_):
            ids = _compact_planes(bits, cap, s)

            def pack_one(ids_d, count_d, prop_d):
                par = prop_d[jnp.clip(ids_d[:cap], 0, s - 1)] - base
                return fmt.pack(ids_d, count_d, payload=par)

            words, meta = jax.vmap(pack_one)(ids, counts, prop)
            r_words = ex.all_to_all(words, fmt=fmt.name).reshape(c, fmt.data_words)
            r_meta = ex.all_to_all(meta, fmt=fmt.name, part="meta").reshape(c, 2)

            def unpack_one(w, m, sender):
                u_ids, u_count, par = fmt.unpack(w, m, fill=s)
                valid = jnp.arange(cap) < u_count
                seg = jnp.where(valid, u_ids[:cap], s)
                glob = par if n_c is None else par + sender * n_c
                val = jnp.where(valid, glob, INF)
                return seg, val

            segs, vals = jax.vmap(unpack_one)(
                r_words, r_meta, jnp.arange(c, dtype=jnp.int32)
            )
            red = jax.ops.segment_min(
                vals.reshape(-1), segs.reshape(-1), num_segments=s + 1
            )
            return red[:s].astype(jnp.int32)

        return run

    branches = [sparse_branch(f) for f in ladder.formats()] + [
        lambda _: alltoall_dense_min(ex, prop)
    ]
    return ex.dispatch(my_bucket, branches, floor=DenseFormat(s).name)


def alltoall_dense_min_planes(ex: AdaptiveExchange, prop: jax.Array) -> jax.Array:
    """Dense int32 all-to-all + min of ``(B, c, s)`` candidate planes."""
    b, c, s = prop.shape
    fmt = DenseFormat(s)
    recv = ex.all_to_all(
        jnp.moveaxis(prop, 0, 1), fmt=fmt.name
    ).reshape(c, b, s)
    return jnp.min(recv, axis=0)


def alltoall_dense_combine_planes(
    ex: AdaptiveExchange, prop: jax.Array, alg
) -> jax.Array:
    """Dense int32 all-to-all + algebra combine of ``(B, c, s)`` planes.

    The semiring-general row exchange: min-algebras reduce exactly like
    :func:`alltoall_dense_min_planes`; sum-algebras (pagerank) decode the
    received partial sums, add across senders and re-encode — the absent
    sentinel 0 decodes to the additive identity, so no masking is needed.
    """
    b, c, s = prop.shape
    fmt = DenseFormat(s)
    recv = ex.all_to_all(
        jnp.moveaxis(prop, 0, 1), fmt=fmt.name
    ).reshape(c, b, s)
    if alg.reduce == "min":
        return jnp.min(recv, axis=0)
    return alg.enc(jnp.sum(alg.dec(recv), axis=0))


def alltoall_min_candidates_planes(
    prop: jax.Array,
    axis,
    ladder: BucketLadder,
    group_size: int,
    *,
    stats: CommStats | None = None,
    phase: str = "bfs/row",
    n_c: int | None = None,
):
    """Adaptive all-to-all + min-reduce of ``(B, c, s)`` candidate planes.

    The batched analog of :func:`alltoall_min_candidates`: B source planes
    share one bucket consensus (max over every (destination, plane) stream)
    and one pair of wire collectives, with per-plane (count, exc) sidebands
    packed one word per plane.  Payload localization is per plane exactly as
    in the single-source exchange — candidates travel column-local and the
    receiver re-globalizes from the all-to-all row index.
    """
    b, c, s = prop.shape
    assert s == ladder.s and c == group_size, (prop.shape, ladder.s, group_size)
    ex = AdaptiveExchange(phase, axis, group_size, ladder, stats, planes=b)
    if not ladder.specs:
        return ex.dispatch(None, [lambda _: alltoall_dense_min_planes(ex, prop)],
                           floor=DenseFormat(s).name)
    assert ladder.payload_width > 0, (
        "row-phase ladder must carry the parent payload: build it with "
        "BucketLadder.default(s, floor_words=s, payload_width=...)"
    )

    prop_t = jnp.moveaxis(prop, 0, 1)  # (c, B, s): all-to-all split layout
    bits = prop_t < INF
    flat = bits.reshape(c * b, s)
    counts, exc_counts = stream_stats(flat)
    my_bucket = jnp.max(jax.vmap(ladder.bucket_for)(counts, exc_counts))
    base = 0 if n_c is None else jax.lax.axis_index(axis) * n_c

    def sparse_branch(fmt: IdStreamFormat):
        cap = fmt.spec.cap

        def run(_):
            ids = _compact_planes(flat, cap, s)

            def pack_one(ids_d, count_d, prop_d):
                par = prop_d[jnp.clip(ids_d[:cap], 0, s - 1)] - base
                return fmt.pack(ids_d, count_d, payload=par)

            words, meta = jax.vmap(pack_one)(
                ids, counts, prop_t.reshape(c * b, s)
            )
            pmeta = pack_plane_meta(meta[:, 0], meta[:, 1]).reshape(c, b)
            r_words = ex.all_to_all(
                words.reshape(c, b, fmt.data_words), fmt=fmt.name
            ).reshape(c, b, fmt.data_words)
            r_meta = ex.all_to_all(pmeta, fmt=fmt.name, part="meta").reshape(c, b)

            def unpack_one(w, m, sender):
                cnt, exc = unpack_plane_meta(m)
                u_ids, u_count, par = fmt.unpack(
                    w, jnp.stack([cnt, exc]), fill=s
                )
                valid = jnp.arange(cap) < u_count
                seg = jnp.where(valid, u_ids[:cap], s)
                glob = par if n_c is None else par + sender * n_c
                val = jnp.where(valid, glob, INF)
                return seg, val

            senders = jnp.broadcast_to(
                jnp.arange(c, dtype=jnp.int32)[:, None], (c, b)
            )
            segs, vals = jax.vmap(jax.vmap(unpack_one))(r_words, r_meta, senders)

            def reduce_plane(seg_p, val_p):  # (c, cap) each
                red = jax.ops.segment_min(
                    val_p.reshape(-1), seg_p.reshape(-1), num_segments=s + 1
                )
                return red[:s].astype(jnp.int32)

            return jax.vmap(reduce_plane)(
                jnp.moveaxis(segs, 0, 1), jnp.moveaxis(vals, 0, 1)
            )

        return run

    branches = [sparse_branch(f) for f in ladder.formats()] + [
        lambda _: alltoall_dense_min_planes(ex, prop)
    ]
    return ex.dispatch(my_bucket, branches, floor=DenseFormat(s).name)


def alltoall_bitmap_min_planes(
    ex: AdaptiveExchange, prop: jax.Array, fmt: BitmapParentFormat,
    n_c: int | None,
) -> jax.Array:
    """Batched bottom-up row exchange: B found-bitmap + packed-parent planes
    per destination chunk, one all-to-all for all of them.  ``n_c=None``
    means the payload is already global (non-id algebras) — no per-sender
    re-globalization."""
    b, c, s = prop.shape
    assert s == fmt.s, (prop.shape, fmt.s)
    prop_t = jnp.moveaxis(prop, 0, 1)  # (c, B, s)
    words = jax.vmap(jax.vmap(fmt.pack))(prop_t)  # (c, B, data_words)
    recv = ex.all_to_all(words, fmt=fmt.name).reshape(c, b, fmt.data_words)
    bits, local = jax.vmap(jax.vmap(fmt.unpack))(recv)  # (c, B, s) each
    sender = jnp.arange(c, dtype=jnp.int32)[:, None, None]
    glob = local if n_c is None else sender * n_c + local
    glob = jnp.where(bits, glob, INF)
    return jnp.min(glob, axis=0).astype(jnp.int32)


def alltoall_bitmap_min(
    ex: AdaptiveExchange, prop: jax.Array, fmt: BitmapParentFormat,
    n_c: int | None,
) -> jax.Array:
    """Bottom-up row exchange: found-bitmap + bit-packed local parents.

    ``prop``: (group_size, s) int32 — *column-local* candidate parents per
    destination owner chunk (INF = no frontier neighbor found).  Each
    sender's subchunk travels as ``s/32`` found bits plus ``payload_width``
    bits per position; the receiver rebuilds global parent ids from the
    sender's grid-column index and min-reduces, reproducing exactly the
    winner the push direction's ``segment_min`` would pick.  ``n_c=None``
    disables the re-globalization for payloads that are already global
    values (non-id min-algebras, e.g. cc labels).
    """
    c, s = prop.shape
    assert s == fmt.s, (s, fmt.s)
    words = jax.vmap(fmt.pack)(prop)  # (c, data_words)
    recv = ex.all_to_all(words, fmt=fmt.name).reshape(c, fmt.data_words)
    bits, local = jax.vmap(fmt.unpack)(recv)  # (c, s) each
    sender = jnp.arange(c, dtype=jnp.int32)[:, None]  # grid-column of origin
    glob = local if n_c is None else sender * n_c + local
    glob = jnp.where(bits, glob, INF)
    return jnp.min(glob, axis=0).astype(jnp.int32)


# ---------------------------------------------------------------------------
# butterfly stages: adaptive merge-exchange of subchunk blocks (ppermute)
# ---------------------------------------------------------------------------


def ppermute_min_block(
    ex: AdaptiveExchange,
    block: jax.Array,
    perm,
    ladder: BucketLadder,
    floor_fmt,
    *,
    gate: jax.Array,
):
    """One butterfly stage: exchange a block of candidate subchunk planes.

    ``block``: (nb, b, s) int32 global candidate parents (INF = none) — the
    ``nb`` subchunks x ``b`` source planes this rank sends to its stage
    partner under ``perm``.  Returns the partner's (nb, b, s) block,
    reconstructed dense so the caller can min-merge it (ButterFly BFS: the
    merged stream is re-bucketed by the NEXT stage's call, so compression
    applies at every hop).

    The wire representation is chosen per stage by the ladder: sparse
    delta+PFOR16 id streams carrying the parent payload at the ladder's
    ``payload_width`` (which must cover GLOBAL ids — merged streams lose
    sender identity, so column-local offsets cannot ride a butterfly), with
    ``floor_fmt`` (found-bitmap + packed parents, or dense int32) as the
    dense floor.  With b > 1 planes the per-stream (count, exc) sidebands
    pack one word per plane (the shared header); the bucket consensus is a
    single round over every plane of every subchunk.  ``gate`` masks the
    consensus contribution of ranks that do not send at this stage (folded
    ranks), so their stale state never inflates the group's bucket choice.
    """
    nb, b, s = block.shape
    flat = block.reshape(nb * b, s)
    bits = flat < INF
    counts, exc_counts = stream_stats(bits)
    if ladder.specs:
        my_bucket = jnp.max(jax.vmap(ladder.bucket_for)(counts, exc_counts))
        my_bucket = jnp.where(gate, my_bucket, 0)
    else:
        my_bucket = None

    def sparse_branch(fmt: IdStreamFormat):
        cap = fmt.spec.cap

        def run(_):
            ids = _compact_planes(bits, cap, s)

            def pack_one(ids_d, count_d, block_d):
                par = block_d[jnp.clip(ids_d[:cap], 0, s - 1)]
                return fmt.pack(ids_d, count_d, payload=par)

            words, meta = jax.vmap(pack_one)(ids, counts, flat)
            if b > 1:
                meta = pack_plane_meta(meta[:, 0], meta[:, 1]).reshape(nb, b)
            words = words.reshape(nb, b, fmt.data_words)
            r_words = ex.ppermute(words, perm, fmt=fmt.name).reshape(
                nb * b, fmt.data_words
            )
            r_meta = ex.ppermute(meta, perm, fmt=fmt.name, part="meta")
            if b > 1:
                cnt, exc = unpack_plane_meta(r_meta.reshape(nb * b))
                r_meta = jnp.stack([cnt, exc], axis=1)

            def unpack_one(w, m):
                u_ids, u_count, par = fmt.unpack(w, m, fill=s)
                valid = jnp.arange(cap) < u_count
                seg = jnp.where(valid, u_ids[:cap], s)
                val = jnp.where(valid, par, INF)
                return jnp.full((s + 1,), INF, jnp.int32).at[seg].min(val)[:s]

            return jax.vmap(unpack_one)(r_words, r_meta).reshape(nb, b, s)

        return run

    def floor_branch(_):
        if isinstance(floor_fmt, BitmapParentFormat):
            words = jax.vmap(floor_fmt.pack)(flat).reshape(nb, b, -1)
            recv = ex.ppermute(words, perm, fmt=floor_fmt.name)
            f_bits, par = jax.vmap(floor_fmt.unpack)(recv.reshape(nb * b, -1))
            return jnp.where(f_bits, par, INF).reshape(nb, b, s)
        return ex.ppermute(block, perm, fmt=floor_fmt.name)

    branches = [sparse_branch(f) for f in ladder.formats()] + [floor_branch]
    return ex.dispatch(my_bucket, branches, floor=floor_fmt.name)


def ppermute_membership_block(
    ex: AdaptiveExchange,
    block: jax.Array,
    perm,
    ladder: BucketLadder,
    *,
    gate: jax.Array,
):
    """One butterfly all-gather stage: exchange a block of membership planes.

    ``block``: (nb, b, s) bool — the ``nb`` chunks x ``b`` source planes
    this rank forwards under ``perm``.  Returns the partner's (nb, b, s)
    bool block.  Sparse stages travel as delta+PFOR16 id streams per
    chunk-plane (with the one-word-per-plane packed sideband when b > 1),
    dense stages as width-1 bitmaps (the doubling block keeps chunk
    identity, so the merge is a plain concatenation/OR into the receiver's
    state).
    """
    nb, b, s = block.shape
    flat = block.reshape(nb * b, s)
    counts, exc_counts = stream_stats(flat)
    if ladder.specs:
        my_bucket = jnp.max(jax.vmap(ladder.bucket_for)(counts, exc_counts))
        my_bucket = jnp.where(gate, my_bucket, 0)
    else:
        my_bucket = None

    def sparse_branch(fmt: IdStreamFormat):
        cap = fmt.spec.cap

        def run(_):
            ids = _compact_planes(flat, cap, s)
            words, meta = jax.vmap(fmt.pack)(ids, counts)
            if b > 1:
                meta = pack_plane_meta(meta[:, 0], meta[:, 1]).reshape(nb, b)
            words = words.reshape(nb, b, fmt.data_words)
            r_words = ex.ppermute(words, perm, fmt=fmt.name).reshape(
                nb * b, fmt.data_words
            )
            r_meta = ex.ppermute(meta, perm, fmt=fmt.name, part="meta")
            if b > 1:
                cnt, exc = unpack_plane_meta(r_meta.reshape(nb * b))
                r_meta = jnp.stack([cnt, exc], axis=1)

            def unpack_one(w, m):
                u_ids, u_count, _ = fmt.unpack(w, m, fill=s)
                valid = jnp.arange(cap) < u_count
                seg = jnp.where(valid, u_ids[:cap], s)
                return jnp.zeros((s + 1,), bool).at[seg].set(True)[:s]

            return jax.vmap(unpack_one)(r_words, r_meta).reshape(nb, b, s)

        return run

    def bitmap_branch(_):
        fmt = BitmapFormat(s)
        words = jax.vmap(fmt.pack)(flat).reshape(nb, b, -1)
        recv = ex.ppermute(words, perm, fmt=fmt.name)
        return jax.vmap(fmt.unpack)(recv.reshape(nb * b, -1)).reshape(nb, b, s)

    branches = [sparse_branch(f) for f in ladder.formats()] + [bitmap_branch]
    return ex.dispatch(my_bucket, branches, floor=BitmapFormat(s).name)


# ---------------------------------------------------------------------------
# beyond-paper: quantized all-reduce for data-parallel gradient sync
# ---------------------------------------------------------------------------


def allreduce_int8(
    x: jax.Array,
    axis,
    group_size: int,
    *,
    stats: CommStats | None = None,
    phase: str = "grad/allreduce",
) -> jax.Array:
    """Two-phase int8-quantized all-reduce (all_to_all scatter + all_gather).

    Phase 1 *scatters* quantized shard-chunks with a tiled ``all_to_all``
    (the static-shape stand-in for reduce_scatter: every rank receives the
    group's copies of its own chunk and sums them locally); phase 2
    re-quantizes the reduced chunk and ``all_gather``\\ s it.  Both wire
    transfers carry int8 payloads + f32 scales per 128 values — ~3.8x fewer
    bytes than an fp32 ring all-reduce.  Lossy; pair with error feedback
    (optim/grad_compress.py).  ``x`` length must divide by
    ``group_size * 128``.
    """
    n = x.shape[0]
    assert n % (group_size * quant.GROUP) == 0, n
    fmt = Int8Format(n)
    ex = AdaptiveExchange(phase, axis, group_size, ladder=None, stats=stats)
    # phase 1: quantize my shard-chunks, scatter-exchange, locally sum my chunk
    chunks = x.reshape(group_size, n // group_size)
    q, sc = jax.vmap(fmt.pack)(chunks)
    q_r = ex.all_to_all(q, fmt=fmt.name, part="q").reshape(group_size, -1)
    sc_r = ex.all_to_all(sc, fmt=fmt.name, part="scales").reshape(group_size, -1)
    partial = jnp.sum(jax.vmap(fmt.unpack)(q_r, sc_r), axis=0)
    # phase 2: quantize reduced chunk, all-gather
    q2, sc2 = fmt.pack(partial)
    q_all = ex.all_gather(q2, fmt=fmt.name, part="q")
    sc_all = ex.all_gather(sc2, fmt=fmt.name, part="scales")
    return fmt.unpack(q_all, sc_all).reshape(x.shape)
