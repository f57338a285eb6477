"""Traversal policies: direction optimization as a first-class layer.

Beamer's direction-optimizing BFS (paper §3.1; Beamer et al. SC'12)
switches between push (top-down) and pull (bottom-up) expansion per level.
In the vectorized TPU formulation both directions touch every edge, so
what survives of the *work* saving is the *representation* switch the
paper builds its compressed exchanges on: sparse levels want packed id
streams and push expansion, dense levels want bitmap wires and pull
expansion.  This module makes that choice a pluggable policy, resolved by
name through :func:`repro.comm.registry.traversal`:

* ``top_down``      — push: frontier sources propose parents
  (a minimum over each destination's edges); the distributed row phase
  exchanges candidate id streams (the ALLTOALLV analog).
* ``bottom_up``     — pull: only unreached destinations accumulate
  candidates, the frontier is probed through its *packed bitmap* (the same
  vertical width-1 gather the ELL expansion uses; the ELL slab version is
  :func:`repro.kernels.spmv.ref.spmv_pull_min_planes`), and the distributed row
  phase swaps the id-stream ALLTOALLV for an unreached-bitmap all-gather
  plus a found-bitmap + bit-packed-parent exchange
  (:class:`repro.comm.BitmapParentFormat`).
* ``direction_opt`` — Beamer-style per-level switch driven by the
  popcount :class:`DensityOracle`, with the switch state threaded through
  the level-loop carry.

The density signal — the frontier popcount against the alpha/beta
thresholds — is the same per-chunk stream count the
:class:`repro.comm.ladder.BucketLadder` buckets on, and the default alpha
is derived from the ladder's largest sparse capacity
(:func:`ladder_alpha`): the traversal flips to pull exactly where the wire
would fall off the id-stream ladder onto its dense floor.  Policy choice
and wire choice therefore come from one oracle.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.comm import registry as wire_registry
from repro.comm.formats import INF
from repro.comm.ladder import BucketLadder
from repro.core import expand as expand_mod
from repro.kernels.bitpack import ops as bp_ops
from repro.kernels.popcount import ops as pc_ops


def ladder_alpha(
    s: int, payload_width: int, threshold=None, default: float = 0.25
) -> float:
    """Bottom-up entry density derived from the row ladder's geometry.

    The pull representation wins exactly when the per-chunk candidate count
    overflows the ladder's largest sparse bucket (the wire would fall to
    the dense floor); below that, packed id streams are cheaper than the
    density-independent bitmap+parent exchange.  ``threshold`` must be the
    same ThresholdPolicy the wire plan's row ladder is built with, so a
    pruned ladder moves the direction switch too.
    """
    ladder = BucketLadder.default(
        s, floor_words=s, payload_width=payload_width, policy=threshold
    )
    return ladder.specs[-1].cap / s if ladder.specs else default


@dataclasses.dataclass(frozen=True)
class DensityOracle:
    """Popcount-based frontier-density oracle (direction AND wire signal).

    ``plane_counts`` is the membership popcount over the packed bitmaps —
    computed by the :mod:`repro.kernels.popcount` kernel in one call over
    all B frontier planes, and the exact quantity the BucketLadder
    thresholds on for the wire representation.  ``next_direction``
    applies alpha/beta hysteresis on the same count, per plane, and accepts the *anticipatory* Beamer signal:
    ``m_f`` (edges incident to the frontier) against ``m_u`` (edges incident
    to still-unreached vertices).  A hub entering the frontier blows up
    ``m_f`` one level before the vertex count crosses ``alpha * n``, so the
    edge rule ``alpha_mf * m_f > m_u`` catches the dense level one step
    earlier than the popcount alone (Beamer et al. SC'12, alpha = 14).
    """

    n: int  # vertex count the density is measured against
    alpha: float = 0.25  # switch to bottom-up above this frontier density
    beta: float = 0.05  # fall back to top-down below this density
    alpha_mf: float = 14.0  # Beamer edge heuristic: enter pull when
    #                         alpha_mf * m_f > m_u (m_f from the degree dot)

    def plane_counts(self, bits: jax.Array) -> jax.Array:
        """Per-plane frontier sizes of ``(B, n)`` membership planes.

        One plane-blocked popcount kernel call covers every source: the
        planes pack through :func:`repro.kernels.bitpack.ops.pack_planes`
        (chunk-aligned flattening) and reduce through ``popcount_planes``.
        """
        b, n = bits.shape
        pad = (-n) % 1024
        if pad:
            bits = jnp.concatenate(
                [bits, jnp.zeros((b, pad), bits.dtype)], axis=1
            )
        words = bp_ops.pack_planes(bits.astype(jnp.uint32), 1)
        return pc_ops.popcount_planes(words)

    def next_direction(self, count, was_bottom_up, m_f=None, m_u=None,
                       growing=None):
        """Hysteresis: enter pull above alpha*n (or on the Beamer edge
        signal when ``m_f``/``m_u`` are provided), leave below beta*n.
        Elementwise over per-source planes.

        The edge rule carries Beamer's growing-frontier guard (``growing``:
        this level's frontier outgrew the last one, SC'12's C_TB condition):
        without it, ``m_u`` collapsing toward zero on the sparse tail of a
        deep traversal makes ``alpha_mf * m_f > m_u`` true on every level
        and the direction flaps into the density-independent pull wire
        where tiny packed id streams would do.
        """
        c = jnp.asarray(count, jnp.float32)
        enter = c > self.alpha * self.n
        if m_f is not None:
            edge = (
                self.alpha_mf * jnp.asarray(m_f, jnp.float32)
                > jnp.asarray(m_u, jnp.float32)
            )
            if growing is not None:
                edge = edge & jnp.asarray(growing, bool)
            enter = enter | edge
        return jnp.where(
            jnp.asarray(was_bottom_up, bool),
            c >= self.beta * self.n,
            enter,
        )


def degree_vector(dst, n_dst: int) -> jax.Array:
    """Per-destination degree of one destination-major edge block (padding
    excluded): the differences of its row offsets.

    The single convention behind the anticipatory oracle on BOTH drivers:
    ``bfs`` feeds the full symmetric edge list (``n_dst == n``), the
    distributed driver its block (``n_r`` bound, followed by a grid-row
    psum) — one definition, so the two m_f signals cannot drift.
    """
    return jnp.diff(expand_mod.row_offsets(dst, n_dst))


def edge_signals(deg, new, parent):
    """Beamer ``(m_f, m_u)`` degree dots over ``(B, n)`` planes.

    ``m_f``: edges incident to the new frontier; ``m_u``: edges incident to
    what remains unreached after this level.  float32 — the dots reach 2m,
    which wraps int32 at Graph500 scales, and the oracle only thresholds
    the ratio.  Shared by both drivers so their direction decisions agree.
    """
    degf = deg.astype(jnp.float32)[None, :]
    m_f = jnp.sum(jnp.where(new, degf, 0.0), axis=1)
    m_u = jnp.sum(jnp.where((parent < 0) & ~new, degf, 0.0), axis=1)
    return m_f, m_u


class DistLevelCtx(NamedTuple):
    """Everything a policy needs to expand one distributed BFS level.

    Built once per rank by :func:`repro.core.distributed_bfs._bfs_local`;
    the exchange callables come from the wire plan
    (:class:`repro.comm.registry.WirePlan`) and the local expansion from
    the expansion backend (:func:`repro.comm.registry.expansion`), so a
    policy never touches a collective primitive or a block data structure
    directly.  All exchange callables are plane-batched: they carry every
    source plane of the batch in one collective.
    """

    expand: object  # ExpansionBackend: push_planes / pull_planes
    block: object  # its LocalBlock (COO edges / ELL slab / hybrid split)
    n_r: int  # row-slice width (destinations per grid row)
    n_c: int  # column-slice width (sources per grid column)
    s: int  # owned-chunk width
    c: int  # grid columns
    col_index: jax.Array  # this rank's grid-column index j
    row_exchange: Callable | None  # push: (B,c,s) global candidates -> (B,s) min
    row_exchange_bu: Callable | None  # pull: (B,c,s) LOCAL candidates -> (B,s)
    unreached_gather: Callable | None  # (B,s) own unreached -> (B,n_r) row slice
    algebra: object = None  # FrontierAlgebra (None = historical min-parent BFS)
    row_base: jax.Array | int = 0  # global id of this rank's first row (i*n_r)


def _phase(ctx: DistLevelCtx, name: str) -> str:
    """Device scope of one gated expansion pass, prefixed like the
    algebra's CommStats phases (``bfs/expand/push``)."""
    return f"{ctx.algebra.name if ctx.algebra is not None else 'bfs'}/{name}"


class TraversalPolicy:
    """One frontier-expansion direction, or a per-level switch over them.

    ``propose_batch`` produces the (B, n) candidate-parent planes for the
    single-device driver (direction_opt runs one gated pass per direction
    so no branch runs that no plane is in); ``expand_dist`` runs local
    expansion + the row exchange inside ``shard_map`` over ALL planes at
    once — ``parent``/``f_col`` carry a leading (B,) plane axis,
    ``use_bu``/``active`` are per-plane flags, and the result is the (B, s)
    min-reduced global candidates for the owned chunk.  Both dispatch the
    *local* expansion through an expansion backend
    (:mod:`repro.core.expand`): the policy owns direction, probe masking,
    and the wire shape; the backend owns the block data structure.  All
    (policy x backend) combinations produce *identical* parent/level
    results — they differ in probe representation and wire shape only.
    """

    name: str = ""
    starts_bottom_up: bool = False
    uses_top_down: bool = True  # driver builds the push row exchange
    uses_bottom_up: bool = False  # driver builds the pull exchanges

    def propose_batch(self, expand, block, value, frontier, use_bu,
                      alg=None, x=None, plane_mask=None):
        """(B, n) candidate planes for the single-device driver.

        ``value`` is the algebra's state plane (the parent vector for BFS);
        ``alg``/``x`` switch value algebras onto the backend's value
        expansion (``x`` = the per-source message operands); ``plane_mask``
        restricts the pull mask to the planes a gated pass serves.
        """
        raise NotImplementedError

    def expand_dist(self, ctx: DistLevelCtx, value, f_col, use_bu, active,
                    x_col=None, plane_mask=None):
        raise NotImplementedError

    def next_direction(self, oracle: DensityOracle, count, use_bu,
                       m_f=None, m_u=None, growing=None):
        """Direction for the next level (fixed for single-direction
        policies); elementwise over the per-source count planes."""
        return jnp.broadcast_to(
            jnp.bool_(self.starts_bottom_up), jnp.shape(count)
        )


class TopDownPolicy(TraversalPolicy):
    name = "top_down"

    def propose_batch(self, expand, block, value, frontier, use_bu,
                      alg=None, x=None, plane_mask=None):
        # push: every frontier source proposes itself (or its value's edge
        # message) to its neighbors
        if alg is None or alg.payload_is_id:
            return expand.push_planes(block, frontier)
        return expand.push_value_planes(block, frontier, x, alg)

    def _propose(self, ctx, f_col, x_col):
        """(B, n_c) frontier planes -> (B, c, s) global candidate planes.

        Id payloads: the backend returns column-LOCAL min candidates; the
        push wire carries global ids, and min commutes with the constant
        shift ``j * n_c``, so globalizing after the min is exact.  Value
        payloads are already global — the backend's value expansion takes
        the id bases only to derive edge messages."""
        alg = ctx.algebra
        if alg is None or alg.payload_is_id:
            local = ctx.expand.push_planes(ctx.block, f_col)  # (B, n_r)
            glob = jnp.where(local < INF, ctx.col_index * ctx.n_c + local, INF)
        else:
            glob = ctx.expand.push_value_planes(
                ctx.block, f_col, x_col, alg,
                row_base=ctx.row_base, col_base=ctx.col_index * ctx.n_c,
            )
        return glob.reshape(-1, ctx.c, ctx.s)

    def expand_dist(self, ctx, value, f_col, use_bu, active,
                    x_col=None, plane_mask=None):
        with jax.named_scope(_phase(ctx, "expand/push")):
            return ctx.row_exchange(self._propose(ctx, f_col, x_col))


class BottomUpPolicy(TraversalPolicy):
    name = "bottom_up"
    starts_bottom_up = True
    uses_top_down = False
    uses_bottom_up = True

    def propose_batch(self, expand, block, value, frontier, use_bu,
                      alg=None, x=None, plane_mask=None):
        # pull: the backend probes the *packed* frontier bitmap (the
        # representation switch; kernels/spmv's vertical width-1 gather, or
        # spmv_pull_min itself on the ELL slab), and only destinations in
        # the algebra's pull mask accumulate candidates
        mask = (value < 0) if alg is None else alg.pull_mask(value)
        if plane_mask is not None:
            mask = mask & plane_mask[:, None]
        if alg is None or alg.payload_is_id:
            return expand.pull_planes(block, frontier, mask)
        return expand.pull_value_planes(block, frontier, mask, x, alg)

    def expand_dist(self, ctx, value, f_col, use_bu, active,
                    x_col=None, plane_mask=None):
        with jax.named_scope(_phase(ctx, "expand/pull")):
            alg = ctx.algebra
            # pull-mask membership of the whole row slice, gathered as bitmap
            # planes over the grid row — this replaces the id-stream ALLTOALLV.
            # Exhausted planes are masked out: their permanent unreached set
            # (often most of the graph) must not escalate the bucket consensus
            # the surviving planes' gather pays for, and the host replay prices
            # inactive planes as empty.
            mask = (value < 0) if alg is None else alg.pull_mask(value)
            pm = active if plane_mask is None else (plane_mask & active)
            unreached = ctx.unreached_gather(mask & pm[:, None])  # (B, n_r) bool
            if alg is None or alg.payload_is_id:
                # candidates stay column-LOCAL so the wire payload bit-packs at
                # the static column-width class; the receiver globalizes per
                # sender
                local = ctx.expand.pull_planes(ctx.block, f_col, unreached)
            else:
                local = ctx.expand.pull_value_planes(
                    ctx.block, f_col, unreached, x_col, alg,
                    row_base=ctx.row_base, col_base=ctx.col_index * ctx.n_c,
                )
            return ctx.row_exchange_bu(local.reshape(-1, ctx.c, ctx.s))


class DirectionOptPolicy(TraversalPolicy):
    """Beamer-style per-level switch between push and pull, per source.

    The per-plane direction flags live in the level-loop carry; both
    branches are in the traced program (``lax.cond``) and the flags are
    group-uniform because they derive from the globally ``psum``-ed
    per-plane frontier counts — the same consensus shape the
    AdaptiveExchange uses for bucket dispatch.  Each source plane switches
    independently: planes routed to the direction a branch does not serve
    ride it as masked (empty) planes, and a branch whose plane set is empty
    is skipped entirely at run time (its collectives still lower, so the
    CommStats ledger and HLO stay 1:1).
    """

    name = "direction_opt"
    uses_top_down = True
    uses_bottom_up = True

    def __init__(self):
        self._td = TopDownPolicy()
        self._bu = BottomUpPolicy()

    def propose_batch(self, expand, block, value, frontier, use_bu,
                      alg=None, x=None, plane_mask=None):
        # mirror expand_dist: ONE gated pass per direction over all planes.
        # A per-plane lax.cond would turn into a select that runs both O(m)
        # expansions every level — even for a scalar root.  Planes routed
        # to the direction a pass does not serve ride it masked-empty, as
        # in the distributed exchange.
        b, n = value.shape
        empty = INF if alg is None else alg.empty
        combine = jnp.minimum if alg is None else alg.combine
        act = jnp.any(frontier, axis=1)
        td_mask = (~use_bu) & act
        bu_mask = use_bu & act
        empty_planes = lambda: jnp.full((b, n), empty, jnp.int32)  # noqa: E731
        td = jax.lax.cond(
            jnp.any(td_mask),
            lambda: self._td.propose_batch(
                expand, block, value, frontier & td_mask[:, None], use_bu,
                alg=alg, x=x,
            ),
            empty_planes,
        )
        # the pull pass's mask is restricted to its planes, so it proposes
        # nothing for planes riding the push direction
        bu = jax.lax.cond(
            jnp.any(bu_mask),
            lambda: self._bu.propose_batch(
                expand, block, value, frontier & bu_mask[:, None], use_bu,
                alg=alg, x=x, plane_mask=bu_mask,
            ),
            empty_planes,
        )
        return combine(td, bu)

    def expand_dist(self, ctx, value, f_col, use_bu, active,
                    x_col=None, plane_mask=None):
        b = value.shape[0]
        alg = ctx.algebra
        empty = INF if alg is None else alg.empty
        combine = jnp.minimum if alg is None else alg.combine
        td_mask = (~use_bu) & active
        bu_mask = use_bu & active
        empty_planes = lambda: jnp.full((b, ctx.s), empty, jnp.int32)  # noqa: E731
        td = jax.lax.cond(
            jnp.any(td_mask),
            lambda: self._td.expand_dist(
                ctx, value, f_col & td_mask[:, None], use_bu, active,
                x_col=x_col,
            ),
            empty_planes,
        )
        # the pull pass's plane mask keeps push-direction planes out of the
        # unreached bitmap (and hence out of the pull wire's content)
        bu = jax.lax.cond(
            jnp.any(bu_mask),
            lambda: self._bu.expand_dist(
                ctx, value, f_col & bu_mask[:, None], use_bu, active,
                x_col=x_col, plane_mask=bu_mask,
            ),
            empty_planes,
        )
        return combine(td, bu)

    def next_direction(self, oracle, count, use_bu, m_f=None, m_u=None,
                       growing=None):
        return oracle.next_direction(count, use_bu, m_f=m_f, m_u=m_u,
                                     growing=growing)


def level_once(src, dst, n, policy: TraversalPolicy, oracle: DensityOracle,
               state, deg=None, expand=None, block=None, alg=None):
    """One single-device traversal level over every source plane.

    The single shared implementation behind ``bfs()`` / ``bfs_levels()`` /
    ``traverse()`` — ``state`` is any NamedTuple with value / level /
    frontier (all ``(B, n)``) / depth / active / use_bu / counts (``(B,)``)
    / aux fields.  The policy proposal runs plane-batched
    (``propose_batch``) through the local-expansion backend ``expand`` over
    its prepared ``block`` (default: the COO backend over the flat
    ``src``/``dst`` edge arrays); the per-plane popcounts come from one
    plane-blocked kernel call.  ``deg``, if given, is the (n,) degree
    vector feeding the anticipatory Beamer ``m_f`` signal (gated on a
    growing frontier, via the counts carry) into the per-plane direction
    decision — and the plus-times algebra's per-source ``x = v/deg``.
    ``alg`` is the :class:`repro.core.algebra.FrontierAlgebra`; ``None``
    keeps the historical min-parent BFS triple.
    """
    if expand is None:
        expand = expand_mod.resolve("coo")
    if block is None:
        block = expand.local_block(src, dst, (), n, n)
    x = None
    if alg is not None and alg.needs_values:
        x = alg.source_values(state.value, deg)
    proposed = policy.propose_batch(
        expand, block, state.value, state.frontier, state.use_bu,
        alg=alg, x=x,
    )
    if alg is None:
        new = (proposed < INF) & (state.value < 0)
        value = jnp.where(new, proposed, state.value)
    else:
        value, new = alg.update(state.value, proposed, state.depth, n)
    counts_new = oracle.plane_counts(new)
    m_f = m_u = growing = None
    if deg is not None and (alg is None or alg.payload_is_id):
        m_f, m_u = edge_signals(deg, new, state.value)
        growing = counts_new > state.counts
    if alg is None:
        aux, frontier, counts = state.aux, new, counts_new
        alive = jnp.any(counts_new > 0)
    else:
        from repro.core.algebra import LOCAL_EXCHANGE

        aux, frontier, counts, alive = alg.post_update(
            LOCAL_EXCHANGE, state.aux, state.value, value, new,
            state.frontier, oracle.plane_counts,
        )
    return state._replace(
        value=value,
        level=jnp.where(new, state.depth + 1, state.level),
        frontier=frontier,
        depth=state.depth + 1,
        active=alive,
        use_bu=policy.next_direction(oracle, counts, state.use_bu,
                                     m_f=m_f, m_u=m_u, growing=growing),
        counts=counts,
        aux=aux,
    )


def resolve(name: str) -> TraversalPolicy:
    """Resolve a policy by name through the unified registry."""
    return wire_registry.traversal(name)


POLICIES = ("top_down", "bottom_up", "direction_opt")

for _p in (TopDownPolicy(), BottomUpPolicy(), DirectionOptPolicy()):
    wire_registry.register_traversal(_p)
del _p
