"""AdaptiveExchange: one engine behind every adaptive collective.

Generalizes the bucket-ladder + ``pmax`` group-consensus + ``lax.switch``
dispatch that the BFS column and row collectives used to hand-roll
separately, and funnels *every* collective primitive through CommStats
byte accounting:

* :meth:`dispatch` — per-rank bucket choice (from the ladder) is made
  group-uniform with a recorded ``pmax``, then ``lax.switch`` runs the
  branch whose collective carries exactly that bucket's words.  A
  single-branch exchange (empty ladder, or a fixed-format plan like the
  int8 gradient all-reduce) skips the consensus entirely — no dead
  all-reduce in the HLO.
* :meth:`all_gather` / :meth:`all_to_all` / :meth:`pmax` / :meth:`psum` /
  :meth:`ppermute` — thin wrappers over ``jax.lax`` that record the
  result-shape bytes of the op they emit, so CommStats entries correspond
  1:1 with the collective ops the dry-run roofline parses out of HLO.

Every record carries two byte counts: ``nbytes`` (result-shape bytes, the
HLO-parity convention ``compare_comm_stats`` checks) and ``moved_bytes``
(what actually crosses a link).  They differ exactly where the HLO operand
over-counts traffic: identity ``ppermute`` pairs (the 2D transpose always
contains self-sends), the own-chunk share of a gather/all-to-all, and the
ring all-reduce's 2(g-1)/g volume.

Recording happens at trace time; every entry's key is static, so
retracing is idempotent (see :mod:`repro.comm.stats`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax

from repro import obs
from repro.comm.ladder import BucketLadder
from repro.comm.stats import CommStats, aval_bytes

CONSENSUS = "consensus"  # fmt label of the bucket-choice all-reduce


@dataclasses.dataclass(frozen=True)
class AdaptiveExchange:
    """One adaptive exchange site: phase name, mesh axis, ladder, stats."""

    phase: str  # logical zone, e.g. "bfs/column"
    axis: Any  # mesh axis name or tuple of names
    group_size: int
    ladder: BucketLadder | None = None  # None -> single fixed format
    stats: CommStats | None = None
    #: number of multi-source frontier planes riding this site's payloads.
    #: With planes > 1 every payload collective is attributed per plane under
    #: sub-zones ``{phase}@p{k}`` (the plane shares divide exactly — every
    #: plane contributes the same word count), while the bucket-consensus
    #: all-reduce stays under the base phase: ONE consensus round serves all
    #: B planes, which is precisely the amortization the ledger must show.
    planes: int = 1

    # -- recording collective primitives ------------------------------------

    def _rec(self, fmt: str, kind: str, part: str, out: jax.Array,
             moved: int | None = None, per_plane: bool = True) -> None:
        if self.stats is None:
            return
        if self.planes > 1 and per_plane:
            nbytes = aval_bytes(out)
            assert nbytes % self.planes == 0, (self.phase, nbytes, self.planes)
            share = nbytes // self.planes
            for k in range(self.planes):
                m = None
                if moved is not None:
                    m = moved // self.planes
                    if k == self.planes - 1:  # keep the moved total exact
                        m += moved - self.planes * (moved // self.planes)
                self.stats.record(f"{self.phase}@p{k}", fmt, kind, part, share,
                                  moved_bytes=m)
        else:
            self.stats.record_aval(self.phase, fmt, kind, part, out,
                                   moved_bytes=moved)

    def _peer_share(self, out: jax.Array) -> int:
        """Result bytes minus the own chunk (gathers/all-to-alls keep 1/g)."""
        return aval_bytes(out) * (self.group_size - 1) // self.group_size

    def all_gather(self, x: jax.Array, *, fmt: str, part: str = "words") -> jax.Array:
        with jax.named_scope(self.phase):
            out = jax.lax.all_gather(x, self.axis, tiled=True)
        self._rec(fmt, "all-gather", part, out, moved=self._peer_share(out))
        return out

    def all_to_all(self, x: jax.Array, *, fmt: str, part: str = "words") -> jax.Array:
        with jax.named_scope(self.phase):
            out = jax.lax.all_to_all(x, self.axis, 0, 0, tiled=True)
        self._rec(fmt, "all-to-all", part, out, moved=self._peer_share(out))
        return out

    def pmax(self, x: jax.Array, *, fmt: str = CONSENSUS, part: str = "bucket") -> jax.Array:
        with jax.named_scope(self.phase):
            out = jax.lax.pmax(x, self.axis)
        # one consensus serves every plane: never split per plane
        self._rec(fmt, "all-reduce", part, out,
                  moved=2 * self._peer_share(out), per_plane=False)
        return out

    def pmin(self, x: jax.Array, *, fmt: str = CONSENSUS, part: str = "bucket") -> jax.Array:
        with jax.named_scope(self.phase):
            out = jax.lax.pmin(x, self.axis)
        # consensus-shaped like pmax (the SSSP window floor rides this)
        self._rec(fmt, "all-reduce", part, out,
                  moved=2 * self._peer_share(out), per_plane=False)
        return out

    def psum(self, x: jax.Array, *, fmt: str, part: str = "value") -> jax.Array:
        with jax.named_scope(self.phase):
            out = jax.lax.psum(x, self.axis)
        self._rec(fmt, "all-reduce", part, out, moved=2 * self._peer_share(out))
        return out

    def ppermute(self, x: jax.Array, perm, *, fmt: str, part: str = "words") -> jax.Array:
        with jax.named_scope(self.phase):
            out = jax.lax.ppermute(x, self.axis, perm)
        # identity pairs (src == dst) emit full HLO operand bytes but move
        # nothing; ranks outside ``perm`` receive zeros without traffic
        n_moved = sum(1 for src, dst in perm if src != dst)
        self._rec(fmt, "collective-permute", part, out,
                  moved=aval_bytes(out) * n_moved // self.group_size)
        return out

    # -- adaptive dispatch ----------------------------------------------------

    def dispatch(
        self,
        local_bucket: jax.Array | None,
        branches: Sequence[Callable[[Any], jax.Array]],
        *,
        floor: str,
    ) -> jax.Array:
        """Group-consensus branch selection.

        ``branches`` is index-aligned with the ladder's sparse formats,
        dense fallback last; ``floor`` names the fallback's wire format
        (required: it labels that branch on the trace).
        ``local_bucket`` is this rank's smallest usable bucket (ignored
        when only one branch exists).  Each branch runs under the device
        scope ``{phase}/{format}``, so a trace shows which bucket ran.
        """
        if len(branches) == 1:
            return branches[0](None)
        assert local_bucket is not None
        names = [f.name for f in self.ladder.formats()] + [floor]
        assert len(names) == len(branches), (names, len(branches))
        bucket = self.pmax(local_bucket)
        return jax.lax.switch(
            bucket,
            [obs.scoped(f"{self.phase}/{n}")(br) for n, br in zip(names, branches)],
            operand=None,
        )
