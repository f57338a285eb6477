"""Pallas TPU kernel: fused block-quantize (max-abs scale + round to int8).

Grid step = ``(ROWS, 128)`` float32 tile -> ``(ROWS, 128)`` int8 tile +
``(ROWS, 1)`` row scales.  ``ROWS = 32`` is the int8 sublane tile, and the
scales leave as a column so that no block is rank 1.  The reduction
(max-abs) and the elementwise scale/round stay in VMEM; on TPU this is one
VPU pass instead of XLA's reduce + broadcast + round chain.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret
from repro.kernels.quant.ref import GROUP

ROWS = 32  # rows per grid step: the int8 (32, 128) tile


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...]  # (ROWS, 128) float32
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0  # (ROWS, 1)
    safe = jnp.where(scale > 0, scale, 1.0)
    q_ref[...] = jnp.clip(jnp.round(x / safe), -127, 127).astype(jnp.int8)
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_pallas(x: jax.Array, interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    interpret = resolve_interpret(interpret)
    n = x.shape[0]
    assert n % (ROWS * GROUP) == 0, n
    grid = n // (ROWS * GROUP)
    x2 = x.astype(jnp.float32).reshape(n // GROUP, GROUP)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((ROWS, GROUP), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((ROWS, GROUP), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n // GROUP, GROUP), jnp.int8),
            jax.ShapeDtypeStruct((n // GROUP, 1), jnp.float32),
        ),
        interpret=interpret,
    )(x2)
    return q.reshape(-1), s.reshape(-1)
