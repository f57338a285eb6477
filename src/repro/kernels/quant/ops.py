"""Dispatch layer for the int8 quant kernel.

On TPU the compiled kernel quantizes every payload: lengths off its
``ROWS * GROUP`` step are zero-padded (zero groups quantize to zero with a
zero scale) and sliced back.  Elsewhere the jnp oracle runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.quant import quant, ref


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    if not _use_pallas():
        return ref.quantize(x)
    n = x.shape[0]
    assert n % ref.GROUP == 0, n
    q, s = quant.quantize_pallas(jnp.pad(x, (0, -n % (quant.ROWS * ref.GROUP))))
    return q[:n], s[: n // ref.GROUP]


dequantize = ref.dequantize
