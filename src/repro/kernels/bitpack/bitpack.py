"""Pallas TPU kernel for vertical bit packing (S4-BP128 analog).

Layout (see :mod:`repro.kernels.bitpack.ref`): with ``K = 32 // b`` values
per word and ``Wc = 32 * b`` words per 1024-value chunk, word ``j`` of chunk
``c`` holds ``chunk_c[k*Wc + j]`` at bit offset ``k*b``.  The wrapper views
the values as the ``(K, W)`` plane matrix ``X[k, c*Wc + j] = chunk_c[k*Wc + j]``
(one XLA transpose of ``(C, K, Wc)``), so packing is an OR over the ``K``
rows of ``X`` with static shifts and unpacking is its inverse.  Every
shift/OR is a full-lane VPU op on a ``(K, TW)`` tile for every width class,
b = 1 included: no lane shuffles, and every block spans the array's full
second-minor dimension, which is what the TPU tiling rule asks of blocks
with fewer than 8 rows.

Validated in interpret mode against :mod:`repro.kernels.bitpack.ref` over a
shape x bit-width sweep (tests/test_kernels.py) and compiled for v5e in
tests/test_chip_compile.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret
from repro.kernels.bitpack.ref import B_CLASSES

#: value-count alignment of the kernels: packed words then fill whole
#: 128-lane rows at every width class (4096 * b / 32 = 128 * b words)
VALS_PER_BLOCK = 4096
#: most packed words (lanes) one grid step covers
MAX_STEP_WORDS = 8192


def _step_words(w: int) -> int:
    assert w % 128 == 0, w
    return math.gcd(w, MAX_STEP_WORDS)


def _to_planes(values: jax.Array, b: int) -> jax.Array:
    k, wc = 32 // b, 32 * b
    return values.reshape(-1, k, wc).transpose(1, 0, 2).reshape(k, -1)


def _from_planes(x: jax.Array, b: int) -> jax.Array:
    k, w = x.shape
    wc = 32 * b
    return x.reshape(k, w // wc, wc).transpose(1, 0, 2).reshape(-1)


def _pack_kernel(x_ref, o_ref, *, b: int):
    x = x_ref[...]  # (K, TW) uint32
    acc = x[0:1, :]
    for k in range(1, 32 // b):
        acc = acc | (x[k : k + 1, :] << jnp.uint32(k * b))
    o_ref[...] = acc


def _unpack_kernel(w_ref, o_ref, *, b: int):
    w = w_ref[...]  # (1, TW) uint32
    k_rows, tw = o_ref.shape
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (k_rows, tw), 0) * jnp.uint32(b)
    o_ref[...] = (w >> shifts) & jnp.uint32((1 << b) - 1)


@functools.partial(jax.jit, static_argnames=("b", "interpret"))
def pack_pallas(values: jax.Array, b: int, interpret: bool | None = None) -> jax.Array:
    """Pack uint32 values (length multiple of 4096) at width ``b``."""
    interpret = resolve_interpret(interpret)
    assert b in B_CLASSES, b
    if b == 32:
        return values.astype(jnp.uint32)
    n = values.shape[0]
    assert n % VALS_PER_BLOCK == 0, n
    k, w = 32 // b, n * b // 32
    tw = _step_words(w)
    out = pl.pallas_call(
        functools.partial(_pack_kernel, b=b),
        grid=(w // tw,),
        in_specs=[pl.BlockSpec((k, tw), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, tw), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, w), jnp.uint32),
        interpret=interpret,
    )(_to_planes(values.astype(jnp.uint32), b))
    return out.reshape(-1)


@functools.partial(jax.jit, static_argnames=("b", "interpret"))
def unpack_pallas(words: jax.Array, b: int, interpret: bool | None = None) -> jax.Array:
    """Inverse of :func:`pack_pallas`."""
    interpret = resolve_interpret(interpret)
    assert b in B_CLASSES, b
    if b == 32:
        return words.astype(jnp.uint32)
    w = words.shape[0]
    assert (w * 32 // b) % VALS_PER_BLOCK == 0, w
    k = 32 // b
    tw = _step_words(w)
    x = pl.pallas_call(
        functools.partial(_unpack_kernel, b=b),
        grid=(w // tw,),
        in_specs=[pl.BlockSpec((1, tw), lambda i: (0, i))],
        out_specs=pl.BlockSpec((k, tw), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, w), jnp.uint32),
        interpret=interpret,
    )(words.astype(jnp.uint32).reshape(1, w))
    return _from_planes(x, b)
