"""2D-partitioned distributed BFS with adaptive compressed collectives
(paper Alg. 4).

One BFS level on the R x C grid (rank (i, j) holds block A_ij, owns vertex
chunk q = i*C + j of width s):

  1. **TransposeVector** (Alg. 2 l.4): ``ppermute`` moves owned frontier
     chunk q to the rank that needs it column-phase (rank (q % R, q // R)).
  2. **column phase** (ALLGATHERV + compress): all-gather of the frontier
     membership over the row axis assembles the column slice f_j; the wire
     representation is chosen per group by the bucket ladder — packed
     delta+PFOR16 id stream when sparse, width-1 bitmap when dense.
  3. **local expansion**: the traversal policy's direction — *push*
     (top-down: min candidate parents over the block's edges,
     t_i = A_ij (x) f_j) or *pull* (bottom-up: only unreached destinations
     accumulate, gated on an unreached-bitmap all-gather over the grid
     row) — dispatched through the *expansion backend* (``cfg.expand``):
     ``coo`` (first-hit scan over the padded edge arrays), ``ell``
     (dense neighbor slabs through the ELL SpMV gather), or ``hybrid``
     (per-block degree split; hubs stay COO).  Expansion is compute-local:
     backend choice changes no collective and no CommStats entry.
  4. **row phase**: top-down exchanges per-destination candidate subchunks
     (ALLTOALLV + compress — ids delta-packed, parent payloads bit-packed);
     bottom-up swaps the id streams for a found-bitmap + bit-packed-parent
     exchange whose wire cost is density-independent.  Receiver min-reduces
     into its owned chunk either way.
  5. frontier/parent/level update, global ``psum`` termination test; for
     ``direction_opt`` the same popcount count drives the next level's
     direction through the carry.

Modes are *wire plans* and traversal directions are *policies*, both
resolved through :mod:`repro.comm.registry`: mode 'raw' (uncompressed — the
paper's Baseline), 'bitmap', 'auto' (bucketed adaptive), 'btfly'
(ButterFly BFS: the row/unreached exchanges become log2(C) staged
``ppermute`` rounds that re-compress the merged stream per hop; any grid
width works — non-power-of-two C folds its overhang ranks into a first
stage) x policy 'top_down', 'bottom_up', 'direction_opt' (Beamer per-level
switch).  Every collective —
including the transpose permute and the termination psum — reports its wire
bytes through :class:`repro.comm.CommStats`, so the accounting can be
checked 1:1 against the collective operand sizes in the lowered HLO
(:func:`repro.launch.roofline.compare_comm_stats`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.comm import AdaptiveExchange, CommStats, ThresholdPolicy
from repro.comm import collectives as comm_cc
from repro.comm import registry as wire_registry
from repro.core import algebra as algebra_mod
from repro.core import bfs, traversal
from repro.core import expand as expand_mod
from repro.core.csr import BlockedGraph, Partition2D

INF = jnp.iinfo(jnp.int32).max


@dataclasses.dataclass(frozen=True)
class DistBFSConfig:
    row_axes: tuple[str, ...] = ("data",)  # mesh axes spanning grid rows (R)
    col_axis: str = "model"  # mesh axis spanning grid columns (C)
    mode: str = "auto"  # wire-plan name: 'raw' | 'bitmap' | 'auto' | 'btfly'
    policy: str = "top_down"  # traversal: 'top_down' | 'bottom_up' | 'direction_opt'
    expand: str = "coo"  # local expansion: 'coo' | 'ell' | 'hybrid' | 'auto'
    #: frontier algebra: 'bfs' | 'sssp' | 'cc' | 'pagerank', or a
    #: FrontierAlgebra instance (custom delta/tol).  Phase names in the
    #: CommStats ledger are prefixed with the algebra's name.
    algebra: object = "bfs"
    alpha: float | None = None  # BU entry density; None = derive from the ladder
    beta: float = 0.05  # BU exit density (hysteresis)
    max_levels: int = 64

    @property
    def all_axes(self) -> tuple[str, ...]:
        return self.row_axes + (self.col_axis,)


def parent_width_class(n_c: int) -> int:
    """Smallest packing class covering column-local parent offsets."""
    from repro.comm.butterfly import width_class

    return width_class(n_c)


class _Carry(NamedTuple):
    value: jax.Array  # (B, s) int32 algebra state plane (BFS: parent ids)
    level: jax.Array  # (B, s) int32
    frontier: jax.Array  # (B, s) bool
    depth: jax.Array
    active: jax.Array  # scalar bool: any plane still expanding
    use_bu: jax.Array  # (B,) bool: plane expands bottom-up next level
    counts: jax.Array  # (B,) int32 global frontier sizes (psum consensus)
    aux: tuple  # algebra-private carry (SSSP's pending set; () otherwise)


def _bfs_local(
    src_l,
    dst_l,
    extra,
    roots,
    *,
    part: Partition2D,
    cfg: DistBFSConfig,
    stats: CommStats | None = None,
    threshold: ThresholdPolicy | None = None,
):
    """Per-rank body (inside shard_map). src_l/dst_l: (1,..,1,e_cap);
    ``extra``: the expansion backend's per-block containers (ELL slab /
    hybrid residue), same leading singleton grid axes; ``roots``: (B,)
    replicated source vertices — the batch dimension B is a first-class
    axis here, carried as (B, s) planes through every phase."""
    grid_nd = len(cfg.row_axes) + 1
    src_l = src_l.reshape(-1)
    dst_l = dst_l.reshape(-1)
    extra = tuple(a.reshape(a.shape[grid_nd:]) for a in extra)
    b = roots.shape[0]
    r, c, s = part.rows, part.cols, part.chunk
    n_r, n_c = part.n_r, part.n_c
    i = jax.lax.axis_index(cfg.row_axes)
    j = jax.lax.axis_index(cfg.col_axis)
    q = i * c + j
    base = q * s
    perm = part.transpose_perm()

    alg = algebra_mod.resolve(cfg.algebra)
    p = alg.name  # CommStats phase prefix ("bfs/..." stays the seed ledger)
    # the row wire's candidate payload: column-local parent offsets for the
    # id algebra, the algebra's value class otherwise
    p_width = alg.row_payload_width(n_c, part.n)

    policy = traversal.resolve(cfg.policy)
    adaptive = policy.uses_top_down and policy.uses_bottom_up
    alpha = cfg.alpha
    if alpha is None:
        # direction switch at the row ladder's sparse-capacity edge: one
        # oracle decides both the wire bucket and the traversal direction
        alpha = traversal.ladder_alpha(s, p_width, threshold=threshold)
    oracle = traversal.DensityOracle(part.n, alpha=alpha, beta=cfg.beta)

    # mode selection through the unified wire-plan registry: the plan builds
    # the adaptive exchanges (ladders, formats, engine, stats) each traversal
    # direction needs at this site; unused directions build nothing, so no
    # dead collectives reach the HLO or the CommStats ledger.  Every builder
    # gets the plane count: B frontier planes share each exchange's header
    # and bucket consensus.
    # Each exchange runs under the device scope of its CommStats phase,
    # its compaction and packing included, so trace and ledger share names.
    plan = wire_registry.wire_plan(cfg.mode)
    column_gather = obs.scoped(f"{p}/column")(plan.build_column(
        s, cfg.row_axes, r, b=b, policy=threshold, stats=stats,
        phase=f"{p}/column",
    ))
    row_exchange = row_exchange_bu = unreached_gather = None
    if policy.uses_top_down:
        row_exchange = obs.scoped(f"{p}/row")(plan.build_row(
            s, cfg.col_axis, c, n_c, p_width, b=b,
            policy=threshold, stats=stats, phase=f"{p}/row", alg=alg,
        ))
    if policy.uses_bottom_up:
        row_exchange_bu = obs.scoped(f"{p}/row-pull")(plan.build_row_bu(
            s, cfg.col_axis, c, n_c, p_width, b=b,
            policy=threshold, stats=stats, phase=f"{p}/row-pull", alg=alg,
        ))
        unreached_gather = obs.scoped(f"{p}/unreached")(plan.build_unreached(
            s, cfg.col_axis, c, b=b,
            policy=threshold, stats=stats, phase=f"{p}/unreached",
        ))
    # non-adaptive exchanges report through the same engine facade; the
    # termination psum carries all B plane counts in one all-reduce (plus,
    # for adaptive policies, a float32 m_f/m_u companion — same total words
    # as stacking, but the edge dots cannot ride int32 at Graph500 scales)
    ex_transpose = AdaptiveExchange(f"{p}/transpose", cfg.all_axes, r * c, None,
                                    stats, planes=b)
    ex_term = AdaptiveExchange(f"{p}/termination", cfg.all_axes, r * c, None,
                               stats, planes=b)
    ex_values = None
    if alg.needs_values:
        # value algebras ride a second column phase: the owned value plane
        # takes the same transpose permute, then a dense int32 all-gather
        # assembles the (B, n_c) source-value slice next to the membership
        # bits (value-plane packing is width-32, so dense IS the packed
        # representation; the ledger prices it under "{p}/values")
        ex_values = AdaptiveExchange(f"{p}/values", cfg.row_axes, r, None,
                                     stats, planes=b)

    deg_own = None
    if (adaptive and alg.payload_is_id) or alg.needs_deg:
        # anticipatory direction oracle (Beamer m_f, id payloads only) and
        # the plus-times algebra's x = v/deg both need the owned-degree
        # vector: psum it ONCE before the level loop — one grid-row
        # all-reduce whose cost is shared by every source plane.  Gated on
        # actual consumption: a recorded-but-dead psum would be DCE'd from
        # the HLO and break the ledger reconciliation.
        ex_degree = AdaptiveExchange(f"{p}/degree", cfg.col_axis, c, None, stats)
        with jax.named_scope(f"{p}/degree"):
            with jax.named_scope(f"{p}/row-offsets"):
                deg_slice = traversal.degree_vector(dst_l, n_r)
            deg_row = ex_degree.psum(deg_slice, fmt="degree")
            deg_own = jax.lax.dynamic_slice(deg_row, (j * s,), (s,))

    # local expansion through the backend: the block containers were built
    # at partition time and sharded next to the COO arrays; expansion is
    # compute-local, so backend choice cannot touch the CommStats ledger
    # or the collectives above.  The row-offset searches (the COO edges'
    # here, the whole block's in the degree vector) run once per call,
    # outside the level loop, under their own scope.
    backend = expand_mod.resolve(cfg.expand)
    with jax.named_scope(f"{p}/row-offsets"):
        block = backend.local_block(src_l, dst_l, extra, n_r, n_c)

    ctx = traversal.DistLevelCtx(
        expand=backend,
        block=block,
        n_r=n_r,
        n_c=n_c,
        s=s,
        c=c,
        col_index=j,
        row_exchange=row_exchange,
        row_exchange_bu=row_exchange_bu,
        unreached_gather=unreached_gather,
        algebra=alg,
        row_base=i * n_r,
    )

    idx_global = base + jnp.arange(s, dtype=jnp.int32)
    roots32 = roots.astype(jnp.int32)

    def level_step(carry: _Carry) -> _Carry:
        # 1. TransposeVector: all B frontier planes in one permute
        bits_t = ex_transpose.ppermute(carry.frontier, perm, fmt="membership")
        # 2. column phase: assemble f_j (B, n_c) membership planes — and,
        # for value algebras, the matching (B, n_c) source-value planes
        f_col = column_gather(bits_t)
        x_col = None
        if alg.needs_values:
            with jax.named_scope(f"{p}/values"):
                x_own = alg.source_values(carry.value, deg_own)
                x_t = ex_transpose.ppermute(x_own, perm, fmt="values")
                x_col = comm_cc.gather_values_planes(ex_values, x_t)
        # 3+4. policy-directed local expansion + row exchange (per-plane
        # direction; planes with empty frontiers ride as masked planes)
        with jax.named_scope(f"{p}/expand"):
            reduced = policy.expand_dist(
                ctx, carry.value, f_col, carry.use_bu, carry.counts > 0,
                x_col=x_col,
            )
        return update(carry, reduced)

    @obs.scoped(f"{p}/update")
    def update(carry: _Carry, reduced) -> _Carry:
        # 5. fold candidates into the owned state through the algebra; the
        # psum-ed improvement counts feed the termination test and (for
        # direction_opt) each plane's direction
        value, new = alg.update(carry.value, reduced, carry.depth, part.n)
        m_f = m_u = None
        if adaptive and alg.payload_is_id:
            lm_f, lm_u = traversal.edge_signals(deg_own, new, carry.value)
            edges = ex_term.psum(
                jnp.stack([lm_f, lm_u], axis=1), fmt="termination", part="edges"
            )
            m_f, m_u = edges[:, 0], edges[:, 1]
        aux, frontier, counts, alive = alg.post_update(
            ex_term, carry.aux, carry.value, value, new, carry.frontier,
            oracle.plane_counts,
        )
        return _Carry(
            value=value,
            level=jnp.where(new, carry.depth + 1, carry.level),
            frontier=frontier,
            depth=carry.depth + 1,
            active=alive & (carry.depth + 1 < cfg.max_levels),
            use_bu=policy.next_direction(oracle, counts, carry.use_bu,
                                         m_f=m_f, m_u=m_u,
                                         growing=counts > carry.counts),
            counts=counts,
            aux=aux,
        )

    hit = idx_global[None, :] == roots32[:, None]  # (B, s)
    value0, frontier0 = alg.init(hit, idx_global, roots32, part.n)
    init = _Carry(
        value=value0,
        level=jnp.where(hit, 0, -1).astype(jnp.int32),
        frontier=frontier0,
        depth=jnp.int32(0),
        active=jnp.bool_(True),
        use_bu=jnp.broadcast_to(jnp.bool_(policy.starts_bottom_up), (b,)),
        counts=jnp.ones((b,), jnp.int32),
        aux=alg.init_aux(frontier0),
    )
    out = jax.lax.while_loop(lambda s_: s_.active, level_step, init)
    return alg.finalize(out.value), out.level, out.depth


def build_bfs(
    mesh: Mesh,
    bg: BlockedGraph | Partition2D,
    cfg: DistBFSConfig | None = None,
    *,
    stats: CommStats | None = None,
    threshold: ThresholdPolicy | None = None,
):
    """Compile the distributed BFS for a mesh. Returns fn(*blocks, root)
    -> (parent, level, n_levels) with outputs sharded over all axes.

    ``blocks`` are the sharded arrays :func:`shard_blocked` produced for
    ``cfg.expand`` — ``(src_l, dst_l)`` for the COO backend (the legacy
    signature), plus the backend's block containers (ELL slab / hybrid
    residue) otherwise; call as ``fn(*shard_blocked(...), root)``.

    ``root`` may be a scalar source (legacy ``(n,)`` outputs) or a ``(B,)``
    batch of distinct sources — batched calls return ``(B, n)`` parent and
    level planes, one consensus round and one wire header per exchange
    serving all B planes.  Roots are validated (dtype, range, duplicates)
    before dispatch; a wrong root fails with a clear error instead of the
    silent wraparound indexing of the ``idx == root`` scatter.

    ``bg`` may be a BlockedGraph (runnable) or a bare Partition2D (dry-run
    lowering against ShapeDtypeStructs).  ``stats``, if given, is filled at
    trace time with one entry per collective op the program emits (idempotent
    across retraces).  ``threshold`` tunes the bucket ladders' break-even
    pruning (default: the TPU-link ThresholdPolicy)."""
    cfg = cfg or DistBFSConfig(
        row_axes=tuple(mesh.axis_names[:-1]), col_axis=mesh.axis_names[-1]
    )
    wire_registry.wire_plan(cfg.mode)  # fail on unknown modes at build time
    policy = wire_registry.traversal(cfg.policy)  # ... and unknown policies
    backend = expand_mod.resolve(cfg.expand)  # ... and unknown backends
    algebra_mod.resolve(cfg.algebra)  # ... and unknown algebras
    part = bg if isinstance(bg, Partition2D) else bg.part
    assert part.rows == functools.reduce(
        lambda a, b: a * b, (mesh.shape[a] for a in cfg.row_axes)
    ), "grid rows must match row-axis product"
    assert part.cols == mesh.shape[cfg.col_axis]
    if cfg.mode in ("bitmap", "auto", "btfly") or policy.uses_bottom_up:
        assert part.chunk % 1024 == 0, (
            f"compressed modes and pull traversal need 1024-multiple chunks "
            f"(got s={part.chunk}); partition with chunk_multiple=1024"
        )

    blk_spec = P(*cfg.row_axes, cfg.col_axis, None)
    extra_specs = tuple(
        P(*cfg.row_axes, cfg.col_axis, *(None,) * nd) for nd in backend.extra_ndims
    )
    out_spec = P(None, cfg.all_axes)  # (B, n) planes, vertex axis sharded

    local = functools.partial(
        _bfs_local, part=part, cfg=cfg, stats=stats, threshold=threshold
    )
    mapped = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(blk_spec, blk_spec, extra_specs, P()),
        out_specs=(out_spec, out_spec, P()),
        # the direction/plane-mask conds mix replicated consensus values with
        # per-rank planes; like the other shard_map callers, skip the
        # varying-axis type check
        check_vma=False,
    )
    jitted = jax.jit(mapped)
    n_blocks = 2 + len(backend.extra_ndims)

    def jit_args(args):
        if len(args) != n_blocks + 1:
            raise TypeError(
                f"expansion backend {backend.name!r} expects "
                f"fn(*{n_blocks} block arrays, root), got {len(args)} args "
                "— pass everything shard_blocked returned"
            )
        *blocks, root = args
        roots = bfs.validate_roots(root, part.n_orig)
        return (blocks[0], blocks[1], tuple(blocks[2:]),
                jnp.atleast_1d(roots)), roots.ndim == 0

    def run(*args):
        jargs, squeeze = jit_args(args)
        parent, level, depth = jitted(*jargs)
        if squeeze:
            return parent[0], level[0], depth
        return parent, level, depth

    # ahead-of-time lowering for the same arguments; a ``.compile()`` of it
    # is the executable later calls with those shapes reuse
    run.lower_call = lambda *args: jitted.lower(*jit_args(args)[0])
    return run


def shard_blocked(mesh: Mesh, bg: BlockedGraph, cfg: DistBFSConfig | None = None):
    """Place the blocked edge arrays — and the expansion backend's block
    containers (ELL slab / hybrid residue for ``cfg.expand``) — on the
    mesh.  Returns ``(src, dst, *backend arrays)``; the COO default keeps
    the legacy two-tuple."""
    cfg = cfg or DistBFSConfig(
        row_axes=tuple(mesh.axis_names[:-1]), col_axis=mesh.axis_names[-1]
    )
    sizes = tuple(mesh.shape[a] for a in cfg.all_axes)
    backend = expand_mod.resolve(cfg.expand)
    containers = backend.block_arrays(bg)  # host build, e.g. hybrid_blocked
    with obs.span("device_put"):
        sharding = NamedSharding(mesh, P(*cfg.row_axes, cfg.col_axis, None))
        src = jax.device_put(bg.src_local.reshape(sizes + (-1,)), sharding)
        dst = jax.device_put(bg.dst_local.reshape(sizes + (-1,)), sharding)
        extra = []
        for a, nd in zip(containers, backend.extra_ndims):
            tail = a.shape[2:]
            assert len(tail) == nd, (a.shape, nd)
            esharding = NamedSharding(
                mesh, P(*cfg.row_axes, cfg.col_axis, *(None,) * nd))
            extra.append(jax.device_put(a.reshape(sizes + tail), esharding))
    return (src, dst, *extra)
