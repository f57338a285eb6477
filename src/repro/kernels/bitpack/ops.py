"""jit'd dispatch layer for the bitpack kernel.

On TPU the compiled Pallas kernel packs every stream: lengths off the
kernel's 4096-value alignment are zero-padded with whole 1024-value chunks,
whose words are sliced off again (chunks pack independently, so the padding
never touches real words).  Elsewhere the pure-jnp oracle runs; the two are
bit-identical (tests sweep shapes x widths).  The public API is what the
compressed collectives call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.bitpack import bitpack, ref


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def pack(values: jax.Array, b: int) -> jax.Array:
    if not _use_pallas():
        return ref.pack(values, b)
    n = values.shape[0]
    assert n % ref.CHUNK == 0, n
    pad = -n % bitpack.VALS_PER_BLOCK
    return bitpack.pack_pallas(jnp.pad(values, (0, pad)), b)[: n * b // 32]


def unpack(words: jax.Array, b: int) -> jax.Array:
    if not _use_pallas():
        return ref.unpack(words, b)
    w = words.shape[0]
    pad = -w % (bitpack.VALS_PER_BLOCK * b // 32)
    return bitpack.unpack_pallas(jnp.pad(words, (0, pad)), b)[: w * 32 // b]


def pack_planes(values: jax.Array, b: int) -> jax.Array:
    """Pack a ``(B, n)`` plane matrix at width ``b`` -> ``(B, n*b/32)`` words.

    The vertical layout packs independent 1024-value chunks, so a
    chunk-aligned plane axis flattens losslessly: the Pallas kernel blocks
    over ``B x words`` in one grid instead of one launch per source plane.
    Requires ``n % 1024 == 0`` (every wire-format plane is chunk-aligned).
    """
    nplanes, n = values.shape
    assert n % ref.CHUNK == 0, (nplanes, n)
    return pack(values.reshape(-1), b).reshape(nplanes, -1)


def unpack_planes(words: jax.Array, b: int) -> jax.Array:
    """Inverse of :func:`pack_planes`: ``(B, W)`` words -> ``(B, W*32/b)``."""
    nplanes, w = words.shape
    assert (w * 32 // b) % ref.CHUNK == 0, (nplanes, w, b)
    return unpack(words.reshape(-1), b).reshape(nplanes, -1)


def pack_sorted_ids(ids: jax.Array, count: jax.Array, b: int) -> jax.Array:
    """Delta + pack a sorted id stream (paper's frontier codec)."""
    return pack(ref.gaps_from_sorted(ids, count), b)


def unpack_sorted_ids(words: jax.Array, count: jax.Array, b: int, fill: int) -> jax.Array:
    return ref.sorted_from_gaps(unpack(words, b), count, fill)


def compressed_words(capacity: int, b: int) -> int:
    """Static packed-word count for an id stream of ``capacity`` values."""
    assert capacity % ref.CHUNK == 0, capacity
    return capacity * b // 32


def compact_ids(mask_bits: jax.Array, capacity: int, fill: int) -> tuple[jax.Array, jax.Array]:
    """Stream-compact a boolean membership vector into sorted ids + count.

    The jit-safe replacement for the GPU warp-scan compaction the paper's
    CUDA kernel uses: the k-th id is where the running count of set bits
    first reaches k — one prefix sum, then a binary search per output slot.
    Nothing is scattered (``jnp.nonzero`` scatters every element into a
    histogram, which serializes on the TPU).  Slots past the count hold
    ``fill``.
    """
    ranks = jnp.cumsum(mask_bits.astype(jnp.int32))
    count = ranks[-1]
    k = jnp.arange(1, capacity + 1, dtype=jnp.int32)
    ids = jnp.searchsorted(ranks, k, side="left").astype(jnp.int32)
    return jnp.where(k <= count, ids, jnp.int32(fill)), count
