"""Bucket ladders: the capacity classes an adaptive exchange may carry.

Runtime variable sizing is replaced by a small ladder of precompiled
capacities.  Every rank computes the smallest bucket that fits its stream;
a ``pmax`` over the collective's axis makes the choice uniform inside each
communicator group; ``lax.switch`` dispatches to the branch whose
collective carries exactly that many words (see
:class:`repro.comm.engine.AdaptiveExchange`).

Bucket pruning is two-fold (the paper's §5.4.3 threshold, resolved at
trace time since all capacities are static):

* a bucket must genuinely undercut the dense floor in wire words, and
* it must win the modeled pack + transmit + unpack race against the dense
  fallback under :class:`repro.comm.threshold.ThresholdPolicy` —
  on a slow-codec/fast-link platform the ladder collapses to the dense
  representation exactly as the paper's break-even predicts.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.comm.formats import IdStreamFormat, IdStreamSpec
from repro.comm.threshold import ThresholdPolicy
from repro.kernels.bitpack import ref as bpref


@dataclasses.dataclass(frozen=True)
class BucketLadder:
    """Sparse-id buckets (ascending capacity) + dense fallback.

    ``s`` = chunk width (multiple of 1024).  ``floor_words`` is the dense
    fallback's wire size: s/32 for membership bitmaps (column phase), s for
    int32 candidate vectors (row phase) — the row phase therefore packs at
    far higher densities.  ``payload_width`` is stored on the ladder: it
    adds per-id payload words (packed parents) to each bucket's wire cost,
    both when pruning buckets and in :meth:`words_for_branch`.
    """

    s: int
    specs: tuple[IdStreamSpec, ...]
    floor_words: int
    payload_width: int = 0

    @classmethod
    def default(
        cls,
        s: int,
        floor_words: int | None = None,
        payload_width: int = 0,
        policy: ThresholdPolicy | None = None,
    ) -> "BucketLadder":
        policy = policy if policy is not None else ThresholdPolicy()
        floor = floor_words if floor_words is not None else s // 32
        caps: list[int] = []
        for frac in (256, 64, 16, 4):
            cap = max(s // frac, bpref.CHUNK)
            cap = min(cap, 1 << 16)
            wire = IdStreamSpec(cap).n_words + cap * payload_width // 32
            # keep buckets that undercut the dense floor AND beat it under
            # the modeled pack+transmit+unpack break-even
            if (
                cap < s
                and cap not in caps
                and wire < floor
                and policy.should_pack(cap, wire, floor, stream_len=s)
            ):
                caps.append(cap)
        return cls(
            s=s,
            specs=tuple(IdStreamSpec(c) for c in sorted(caps)),
            floor_words=floor,
            payload_width=payload_width,
        )

    @property
    def n_branches(self) -> int:
        return len(self.specs) + 1  # + dense fallback

    def bucket_for(self, count: jax.Array, exc_count: jax.Array) -> jax.Array:
        """Smallest usable bucket index for this rank (before pmax)."""
        b = jnp.int32(len(self.specs))  # dense fallback
        for i in range(len(self.specs) - 1, -1, -1):
            ok = (count <= self.specs[i].cap) & (exc_count <= self.specs[i].exc_cap)
            b = jnp.where(ok, jnp.int32(i), b)
        return b

    def words_for_branch(self, i: int) -> int:
        """Wire words of branch ``i`` (payload priced at the stored width)."""
        if i < len(self.specs):
            return self.specs[i].n_words + self.specs[i].cap * self.payload_width // 32
        return self.floor_words

    def formats(self) -> tuple[IdStreamFormat, ...]:
        """One sparse wire format per bucket (payload width baked in)."""
        return tuple(IdStreamFormat(spec, self.payload_width) for spec in self.specs)


def stream_stats(bits: jax.Array):
    """(count, exception count) of a membership vector's gap stream.

    A set bit's gap is its distance to the previous set bit (to 0 for the
    first), and an exception is a gap that overflows the 16 low bits —
    what bucket selection needs, found with running maxima instead of
    compacting the stream; sparse branches compact only their capacity."""
    idx = jnp.arange(bits.shape[-1], dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(bits, idx, 0), axis=bits.ndim - 1)
    prev = jnp.concatenate(
        [jnp.zeros(bits.shape[:-1] + (1,), jnp.int32), last[..., :-1]], axis=-1
    )
    count = jnp.sum(bits, axis=-1, dtype=jnp.int32)
    exc_count = jnp.sum(bits & (idx - prev >= 1 << 16), axis=-1, dtype=jnp.int32)
    return count, exc_count
