"""Single-device level-synchronous BFS (paper Algorithm 2, one processor).

Edge-centric SpMV formulation: one BFS level is a per-destination minimum of
candidate parents over the symmetric COO edge list — the linear-algebra view
the paper itself uses (``t = A (x) f`` over a min-parent semiring), with the
GPU warp-queue mechanics replaced by fully-vectorizable scans over the
destination-major edge list (DESIGN.md §3, hardware adaptation).

Direction optimization (Beamer, paper §3.1) is a *policy*, resolved through
:mod:`repro.core.traversal`: ``top_down`` pushes from the frontier,
``bottom_up`` pulls through the packed frontier bitmap into unreached
vertices, and ``direction_opt`` switches per level on the popcount density
oracle, anticipated one level early by the Beamer ``m_f`` edge signal (the
degree vector is computed once before the level loop).  In the vectorized
formulation both directions touch all edges, so the *work* saving of
bottom-up does not apply; what survives on TPU is the *representation*
switch (dense bitmap vs sparse id list) which drives the compressed-exchange
bucket choice in the distributed version.  All policies return identical
parent/level arrays.

**Multi-source batches**: ``root`` may be a scalar (legacy single-source
shapes) or a ``(B,)`` vector of sources.  Batched runs widen every carry to
a leading plane axis — parent/level/frontier become ``(B, n)``, the
direction flag becomes per-source — and the level loop runs until every
plane's frontier is empty.  Results per plane are identical to ``B``
independent single-source runs.
"""

from __future__ import annotations

import functools
import weakref
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import algebra as algebra_mod
from repro.core import expand as expand_mod
from repro.core import traversal

INF = jnp.iinfo(jnp.int32).max


class BFSResult(NamedTuple):
    parent: jax.Array  # (n,) | (B, n) int32, -1 = unreached, parent[root] = root
    level: jax.Array  # (n,) | (B, n) int32, -1 = unreached
    n_levels: jax.Array  # scalar int32 (batched: depth of the longest plane)


class _State(NamedTuple):
    value: jax.Array  # (B, n) algebra state plane (BFS: parent ids)
    level: jax.Array  # (B, n)
    frontier: jax.Array  # (B, n) bool
    depth: jax.Array
    active: jax.Array  # scalar bool: any plane still expanding
    use_bu: jax.Array  # (B,) bool: plane expands bottom-up next level
    counts: jax.Array  # (B,) int32 frontier sizes (m_f growing-guard carry)
    aux: tuple  # algebra-private carry (SSSP's pending set; () otherwise)


def validate_roots(roots, n: int):
    """Check root vertices (dtype, range, duplicates) -> int32 array.

    Shared by ``bfs()`` and the distributed driver.  Concrete inputs fail
    fast with a clear error instead of silently wrapping around in the
    ``parent.at[root]`` scatter; traced values (calls from inside ``jit``)
    skip the value checks but keep the shape/dtype contract.
    """
    if isinstance(roots, jax.core.Tracer):
        if roots.ndim > 1:
            raise ValueError(f"roots must be a scalar or (B,) vector, got "
                             f"shape {roots.shape}")
        if not jnp.issubdtype(roots.dtype, jnp.integer):
            raise TypeError(f"roots must be integers, got {roots.dtype}")
        if roots.ndim == 1 and roots.shape[0] == 0:  # static even when traced
            raise ValueError("roots must name at least one source vertex")
        return roots.astype(jnp.int32)
    arr = np.asarray(roots)
    if arr.ndim > 1:
        raise ValueError(f"roots must be a scalar or (B,) vector, got "
                         f"shape {arr.shape}")
    if not np.issubdtype(arr.dtype, np.integer):
        raise TypeError(f"roots must be integers, got {arr.dtype}")
    if arr.size == 0:
        raise ValueError("roots must name at least one source vertex")
    if arr.min(initial=0) < 0 or arr.max(initial=0) >= n:
        bad = arr[(arr < 0) | (arr >= n)]
        raise ValueError(
            f"roots out of range [0, {n}): {np.atleast_1d(bad)[:8].tolist()}"
        )
    if arr.ndim == 1 and np.unique(arr).size != arr.size:
        vals, counts = np.unique(arr, return_counts=True)
        raise ValueError(
            f"duplicate roots in batch: {vals[counts > 1][:8].tolist()} "
            "(each source plane must have a distinct root)"
        )
    return jnp.asarray(arr, jnp.int32)


def hub_roots(degrees, n_roots: int) -> np.ndarray:
    """The ``n_roots`` highest-degree vertices (stable order, argmax first).

    The one root-selection convention for multi-source batches: hub roots
    reach the dense core at the same depth, so the B frontier trajectories
    stay bucket-aligned and the shared-header amortization is not washed
    out by consensus escalation across planes.  Shared by the benchmark's
    acceptance rows (``benchmarks.bfs_comm.batch_roots``) and the example
    driver, so their batches name the same sources.
    """
    order = np.argsort(-np.asarray(degrees), kind="stable")
    return order[:n_roots].astype(np.int64)


def _init_state(roots: jax.Array, n: int, policy: traversal.TraversalPolicy,
                alg: algebra_mod.FrontierAlgebra) -> _State:
    b = roots.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    hit = idx[None, :] == roots[:, None]
    value, frontier = alg.init(hit, idx, roots.astype(jnp.int32), n)
    return _State(
        value=value,
        level=jnp.where(hit, 0, -1).astype(jnp.int32),
        frontier=frontier,
        depth=jnp.int32(0),
        active=jnp.bool_(True),
        use_bu=jnp.broadcast_to(jnp.bool_(policy.starts_bottom_up), (b,)),
        counts=jnp.ones((b,), jnp.int32),
        aux=alg.init_aux(frontier),
    )


_extra_cache = None  # (backend name, src ref, dst ref, n, device arrays)


def _expansion_extra(src, dst, n: int, expand: str):
    """Host-side backend containers for the single-device driver.

    The COO backend needs nothing beyond the edge arrays; ELL/hybrid build
    their slab/residue containers from the *concrete* edge list — calling
    with traced arrays fails with a clear error instead of a silent
    retrace-time rebuild.  The most recent build is cached by graph
    identity (weakrefs, mirroring the distributed driver's container
    cache) so a Graph500-style loop over many roots pays the O(m) numpy
    build and the host->device transfer once.
    """
    global _extra_cache
    backend = expand_mod.resolve(expand)
    if isinstance(src, jax.core.Tracer) or isinstance(dst, jax.core.Tracer):
        if backend.name != "coo":
            raise TypeError(
                f"expansion backend {expand!r} builds its block containers "
                "from concrete edge arrays; call bfs() outside jit or use "
                "expand='coo'"
            )
        return ()
    if backend.name == "coo":
        return ()
    c = _extra_cache
    if (c is not None and c[0] == backend.name and c[1]() is src
            and c[2]() is dst and c[3] == n):
        return c[4]
    extra = tuple(
        jnp.asarray(a) for a in backend.graph_arrays(np.asarray(src), np.asarray(dst), n)
    )
    try:
        _extra_cache = (backend.name, weakref.ref(src), weakref.ref(dst), n, extra)
    except TypeError:
        pass  # plain numpy inputs are not weakref-able; skip caching
    return extra


@functools.partial(
    jax.jit, static_argnames=("n", "policy", "max_levels", "expand", "algebra")
)
def _bfs_batched(src, dst, roots, n, policy, max_levels, expand, extra,
                 algebra="bfs"):
    pol = traversal.resolve(policy)
    alg = algebra_mod.resolve(algebra)
    backend = expand_mod.resolve(expand)
    src, dst = expand_mod.dst_major(src, dst)
    row_ptr = expand_mod.row_offsets(dst, n)
    block = backend.local_block(src, dst, row_ptr, extra, n, n)
    oracle = traversal.DensityOracle(n)
    # the degree vector is computed once before the level loop, and only
    # when something consumes it: the anticipatory direction oracle or the
    # plus-times algebra's x = v/deg source messages
    deg = None
    if (pol.uses_top_down and pol.uses_bottom_up) or alg.needs_deg:
        deg = traversal.degree_vector(row_ptr)
    out = jax.lax.while_loop(
        lambda s: s.active & (s.depth < max_levels),
        lambda s: traversal.level_once(src, dst, n, pol, oracle, s, deg=deg,
                                       expand=backend, block=block, alg=alg),
        _init_state(roots, n, pol, alg),
    )
    return BFSResult(parent=alg.finalize(out.value), level=out.level,
                     n_levels=out.depth)


def bfs(
    src: jax.Array,
    dst: jax.Array,
    root: jax.Array,
    n: int,
    policy: str = "top_down",
    max_levels: int = 64,
    expand: str = "coo",
    algebra="bfs",
) -> BFSResult:
    """BFS over a symmetric COO edge list (padding edges may use src=dst=n).

    Args:
      src/dst: (m,) int32 edge endpoints; entries equal to ``n`` are padding.
      root: scalar int32 source vertex, or a ``(B,)`` batch of distinct
        sources — batched runs return ``(B, n)`` parent/level planes, each
        identical to the corresponding single-source run.
      n: vertex count (static).
      policy: traversal policy name (see :mod:`repro.core.traversal`).
      max_levels: depth cap on the level loop — the same guard (and the
        same default) the distributed driver's ``DistBFSConfig.max_levels``
        applies, so an adversarial high-diameter edge list (a path graph,
        say) cannot keep the ``while_loop`` spinning for O(n) iterations.
        Vertices beyond the cap stay unreached (parent/level = -1); a
        truncated run is detectable as ``n_levels == max_levels`` — raise
        the cap for legitimately high-eccentricity graphs.
      expand: local-expansion backend name (``coo`` | ``ell`` | ``hybrid``
        | ``auto``, see :mod:`repro.core.expand`) — all backends return
        bit-identical parent/level arrays.
      algebra: frontier algebra name or instance (``bfs`` | ``sssp`` |
        ``cc`` | ``pagerank``, see :mod:`repro.core.algebra`).  For value
        algebras the ``parent`` field of the result carries the finalized
        value plane (SSSP distances, CC labels, PageRank scores) and
        ``level`` the round each vertex last improved.
    """
    roots = validate_roots(root, n)
    squeeze = roots.ndim == 0
    extra = _expansion_extra(src, dst, n, expand)
    res = _bfs_batched(
        src, dst, jnp.atleast_1d(roots), n, policy, max_levels, expand, extra,
        algebra=algebra,
    )
    if squeeze:
        return BFSResult(res.parent[0], res.level[0], res.n_levels)
    return res


@functools.partial(
    jax.jit, static_argnames=("n", "max_levels", "policy", "expand")
)
def _bfs_levels_batched(src, dst, roots, n, max_levels, policy, expand, extra):
    pol = traversal.resolve(policy)
    alg = algebra_mod.resolve("bfs")
    backend = expand_mod.resolve(expand)
    src, dst = expand_mod.dst_major(src, dst)
    row_ptr = expand_mod.row_offsets(dst, n)
    block = backend.local_block(src, dst, row_ptr, extra, n, n)
    oracle = traversal.DensityOracle(n)
    deg = None
    if pol.uses_top_down and pol.uses_bottom_up:
        deg = traversal.degree_vector(row_ptr)

    def body(state, _):
        state = jax.lax.cond(
            state.active,
            lambda s: traversal.level_once(src, dst, n, pol, oracle, s, deg=deg,
                                           expand=backend, block=block, alg=alg),
            lambda s: s._replace(active=jnp.bool_(False)),
            state,
        )
        return state, jnp.sum(state.frontier.astype(jnp.int32), axis=1)

    out, sizes = jax.lax.scan(
        body, _init_state(roots, n, pol, alg), None, length=max_levels
    )
    return BFSResult(parent=out.value, level=out.level, n_levels=out.depth), sizes


def bfs_levels(
    src: jax.Array,
    dst: jax.Array,
    root: jax.Array,
    n: int,
    max_levels: int = 64,
    policy: str = "top_down",
    expand: str = "coo",
) -> tuple[BFSResult, jax.Array]:
    """BFS + per-level frontier sizes (drives representation choice stats).

    The ``scan`` length doubles as the depth cap: levels beyond
    ``max_levels`` are never expanded, mirroring ``bfs()``'s guard.
    Batched roots return per-plane size columns: ``sizes[l, k]`` is plane
    ``k``'s frontier size after level ``l+1``.
    """
    roots = validate_roots(root, n)
    squeeze = roots.ndim == 0
    extra = _expansion_extra(src, dst, n, expand)
    res, sizes = _bfs_levels_batched(
        src, dst, jnp.atleast_1d(roots), n, max_levels, policy, expand, extra
    )
    if squeeze:
        return BFSResult(res.parent[0], res.level[0], res.n_levels), sizes[:, 0]
    return res, sizes
