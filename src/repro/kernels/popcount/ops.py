"""Dispatch layer for the popcount kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.popcount import popcount, ref


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def popcount_planes(words: jax.Array) -> jax.Array:
    """Per-plane popcounts of a ``(B, W)`` word matrix (any ``W``: each
    plane is zero-padded to the kernel's 1024-word block geometry).

    The multi-source frontier counter: one call reduces every source plane's
    packed bitmap, blocking the Pallas grid over ``B x words`` instead of
    looping over sources.  On TPU the compiled kernel always runs; elsewhere
    the jnp oracle does.
    """
    b, w = words.shape
    pad = (-w) % popcount.WORDS_PER_BLOCK
    if pad:
        words = jnp.concatenate(
            [words, jnp.zeros((b, pad), words.dtype)], axis=1
        )
    if _use_pallas():
        return popcount.popcount_planes_pallas(words)
    return jnp.sum(ref.popcount_words(words), axis=1)


popcount_words = ref.popcount_words
popcount_total = ref.popcount_total
