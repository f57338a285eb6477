"""Pallas TPU kernel: blocked SWAR popcount with in-VMEM reduction.

Grid = (planes, word tiles).  Each step reads a ``(TR, 128)`` uint32 tile,
counts bits with SWAR arithmetic and folds the tile's rows into the plane's
``(8, 128)`` int32 lane partials, which stay resident across the plane's
word tiles (output revisiting).  HBM traffic is one read of the bitmaps
plus one ``(8, 128)`` write per plane; the wrapper's final sum of 1024
partials per plane is negligible.  Every block is a multiple of the
``(8, 128)`` int32 tile, as the TPU lowering requires.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

TILE = (8, 128)
WORDS_PER_BLOCK = TILE[0] * TILE[1]
#: most 128-word rows one grid step reads (256 KiB of uint32)
MAX_STEP_ROWS = 512


def _popcount_kernel(w_ref, o_ref):
    j = pl.program_id(1)
    v = w_ref[...]
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    counts = ((v * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)
    part = jnp.sum(counts.reshape(-1, *TILE), axis=0)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = part

    @pl.when(j > 0)
    def _acc():
        o_ref[...] += part


@functools.partial(jax.jit, static_argnames=("interpret",))
def popcount_planes_pallas(
    words: jax.Array, interpret: bool | None = None
) -> jax.Array:
    """Per-plane popcounts of a ``(B, W)`` word matrix, ``W % 1024 == 0``.

    The multi-source batch axis is the leading grid dimension, so every
    plane's bitmap is reduced by the same SWAR kernel in one launch.
    Returns ``(B,)`` int32 totals.
    """
    interpret = resolve_interpret(interpret)
    b, w = words.shape
    assert w % WORDS_PER_BLOCK == 0, (b, w)
    rows = w // TILE[1]
    tr = math.gcd(rows, MAX_STEP_ROWS)
    steps = rows // tr
    w2 = words.astype(jnp.uint32).reshape(b * rows, TILE[1])
    out = pl.pallas_call(
        _popcount_kernel,
        grid=(b, steps),
        in_specs=[pl.BlockSpec((tr, TILE[1]), lambda p, j: (p * steps + j, 0))],
        out_specs=pl.BlockSpec(TILE, lambda p, j: (p, 0)),
        out_shape=jax.ShapeDtypeStruct((b * TILE[0], TILE[1]), jnp.int32),
        interpret=interpret,
    )(w2)
    return jnp.sum(out.reshape(b, -1), axis=1)
