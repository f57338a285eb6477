"""Local-expansion backends: hybrid == ell == coo, plus wire invariance.

The contract has two halves.  *Equivalence*: every expansion backend
produces bit-identical parent/level arrays for every traversal policy,
every wire plan, and batched roots — each row's edge set lives in exactly
one structure (ELL slab or COO residue) and the min-parent semiring
commutes with the split.  *Invariance*: expansion is compute-local, so the
CommStats ledger and the collectives in the lowered HLO must be
byte-identical across backends — a backend that touched the wire would be
a correctness bug in the communication accounting.
"""

import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.comm import registry as wire_registry
from repro.core import bfs, expand
from repro.graphgen import builder, kronecker
from repro.kernels.bitpack import ref as bpref
from repro.kernels.spmv import ref as spmv_ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BACKENDS = expand.BACKENDS


def _device_graph(g):
    return jnp.asarray(g.src.astype(np.int32)), jnp.asarray(g.dst.astype(np.int32))


def test_backends_registered():
    assert set(wire_registry.available_expansions()) >= set(BACKENDS)
    assert expand.resolve("auto").name == "hybrid"  # the example's alias
    with pytest.raises(KeyError):
        wire_registry.expansion("csr5")
    with pytest.raises(KeyError):
        expand.resolve("csr5")


def test_ell_from_edges_degree_split():
    """Rows at or under the split live entirely in the slab, heavier rows
    entirely in the residue, and the union is exactly the valid edge set."""
    rng = np.random.default_rng(0)
    n_rows, n_cols, m = 64, 48, 400
    src = rng.integers(0, n_cols + 1, m)  # includes sentinel edges
    dst = rng.integers(0, n_rows + 1, m)
    k = 8
    nbr, res_s, res_d = builder.ell_from_edges(src, dst, n_rows, n_cols, k)
    valid = (src < n_cols) & (dst < n_rows)
    deg = np.bincount(dst[valid], minlength=n_rows)
    for r in range(n_rows):
        slab_row = nbr[r][nbr[r] < n_cols]
        want = np.sort(src[valid & (dst == r)])
        if deg[r] <= k:
            np.testing.assert_array_equal(np.sort(slab_row), want)
            assert not (res_d == r).any()
        else:
            assert slab_row.size == 0
            np.testing.assert_array_equal(np.sort(res_s[res_d == r]), want)
    # width override pads, never truncates
    wide, _, _ = builder.ell_from_edges(src, dst, n_rows, n_cols, k, width=k + 8)
    np.testing.assert_array_equal(wide[:, :k], nbr)
    assert (wide[:, k:] == n_cols).all()


def test_select_split_k_waste_budget():
    """The auto selector keeps slab waste under the budget and moves up on
    uniform degrees; a lone hub cannot drag the split to its own degree."""
    uniform = np.full(256, 16)
    assert builder.select_split_k(uniform, waste_budget=0.5) == 16
    skew = np.full(256, 5)
    skew[0] = 200  # hub
    k = builder.select_split_k(skew, waste_budget=0.5)
    assert k < 200
    covered = (skew[skew <= k]).sum()
    assert covered >= 0.5 * skew.size * k  # waste(k) <= 0.5
    # near-empty block: fall back to the minimal slab
    assert builder.select_split_k(np.zeros(128, np.int64)) == 8
    assert builder.select_split_k(np.ones(128, np.int64), waste_budget=0.01) == 8


def test_blocked_containers_cover_every_edge():
    """ELLBlocks/HybridBlocks at partition time: static shapes, sentinels,
    and slab+residue exactly re-covering each block's edges."""
    from repro.core import csr as csrmod

    g = builder.build_csr(kronecker.kronecker_edges(9, seed=2), n=1 << 9)
    bg = csrmod.partition_2d(g, rows=2, cols=2)
    part = bg.part
    ell = csrmod.ell_blocked(bg)
    hyb = csrmod.hybrid_blocked(bg)
    assert ell.nbr.shape[:3] == (2, 2, part.n_r) and ell.k % 8 == 0
    assert hyb.nbr.shape[:3] == (2, 2, part.n_r) and hyb.k % 8 == 0
    assert hyb.res_src.shape == hyb.res_dst.shape == (2, 2, hyb.r_cap)
    assert hyb.k <= ell.k  # the split never exceeds the max degree
    assert (hyb.padding_ratio() <= ell.padding_ratio() + 1e-9).all()
    for i in range(2):
        for j in range(2):
            s_l, d_l = bg.src_local[i, j], bg.dst_local[i, j]
            valid = (s_l < part.n_c) & (d_l < part.n_r)
            want = set(zip(s_l[valid].tolist(), d_l[valid].tolist()))
            for blocks in (ell, hyb):
                rows, slots = np.nonzero(blocks.nbr[i, j] < part.n_c)
                got = set(zip(blocks.nbr[i, j][rows, slots].tolist(), rows.tolist()))
                if hasattr(blocks, "res_src"):
                    rs, rd = blocks.res_src[i, j], blocks.res_dst[i, j]
                    rv = rs < part.n_c
                    got |= set(zip(rs[rv].tolist(), rd[rv].tolist()))
                assert got == want, (i, j, type(blocks).__name__)


def _graph_with_empty_block():
    """Edges among the first 40 vertices only: empty rows everywhere past
    them, and on any grid the blocks away from the first row and column
    hold no edge at all."""
    rng = np.random.default_rng(11)
    return builder.build_csr(rng.integers(0, 40, size=(120, 2)), n=1 << 11)


@pytest.mark.parametrize("graph", ["kronecker", "empty_block"])
@pytest.mark.parametrize("grid", [(1, 1), (2, 2), (2, 3)], ids=["1x1", "2x2", "2x3"])
def test_host_row_offsets_equal_device_search(grid, graph):
    """The row offsets built on the host at set-up (every block's, and the
    hybrid residue's) equal the device binary search over the same
    destination arrays, element for element, sentinel tail included."""
    from repro.core import csr as csrmod

    if graph == "kronecker":  # hub-heavy
        g = builder.build_csr(kronecker.kronecker_edges(10, seed=6), n=1 << 10)
    else:
        g = _graph_with_empty_block()
    rows, cols = grid
    bg = csrmod.partition_2d(g, rows=rows, cols=cols, chunk_multiple=64)
    hyb = csrmod.hybrid_blocked(bg)
    n_r = bg.part.n_r
    assert bg.row_ptr.shape == hyb.res_row_ptr.shape == (rows, cols, n_r + 1)
    assert bg.row_ptr.dtype == hyb.res_row_ptr.dtype == np.int32
    if graph == "empty_block":
        assert (bg.degrees() == 0).any()
        assert (bg.e_counts == 0).any() == (rows * cols > 1)
    for i in range(rows):
        for j in range(cols):
            for got, dst in ((bg.row_ptr, bg.dst_local), (hyb.res_row_ptr, hyb.res_dst)):
                want = expand.row_offsets(jnp.asarray(dst[i, j]), n_r)
                np.testing.assert_array_equal(got[i, j], np.asarray(want),
                                              err_msg=f"{grid} block {i},{j}")
            assert bg.row_ptr[i, j, -1] == bg.e_counts[i, j]
            assert hyb.res_row_ptr[i, j, -1] == (hyb.res_dst[i, j] < n_r).sum()


@pytest.mark.parametrize("backend", BACKENDS)
def test_distributed_program_never_searches_row_offsets(backend, monkeypatch):
    """The distributed program reads the offsets placed at set-up: tracing
    it (direction_opt, so the degree vector is built too) never calls the
    device search."""
    import jax

    from repro.core import csr as csrmod, distributed_bfs as dbfs

    g = builder.build_csr(kronecker.kronecker_edges(8, seed=1), n=256)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    bg = csrmod.partition_2d(g, rows=1, cols=1)
    cfg = dbfs.DistBFSConfig(mode="auto", policy="direction_opt", expand=backend)
    fn = dbfs.build_bfs(mesh, bg, cfg)
    blocks = dbfs.shard_blocked(mesh, bg, cfg)

    def searched(*args, **kwargs):
        raise AssertionError("expand.row_offsets called in the distributed program")

    monkeypatch.setattr(expand, "row_offsets", searched)
    assert "while" in fn.lower_call(*blocks, jnp.int32(3)).as_text()


def _python_spmv_oracle(nbr, bits, n_cols):
    out = np.full(nbr.shape[0], spmv_ref.INF, np.int64)
    for r in range(nbr.shape[0]):
        for d in range(nbr.shape[1]):
            v = nbr[r, d]
            if v < n_cols and bits[v]:
                out[r] = min(out[r], v)
    return out


@pytest.mark.parametrize("density", [0.0, 0.02, 0.3, 1.0])
def test_coo_first_hit_matches_segment_min(density):
    """On a destination-major block (as ``partition_2d`` lays it out), the
    first-hit scan equals the scatter formulation: push per destination,
    pull masked to unreached rows, and the degree vector from row offsets."""
    from repro.core import csr as csrmod
    from repro.core import traversal

    g = builder.build_csr(kronecker.kronecker_edges(10, seed=4), n=1 << 10)
    bg = csrmod.partition_2d(g, rows=2, cols=2, chunk_multiple=64)
    part = bg.part
    rng = np.random.default_rng(int(density * 100))
    backend = expand.resolve("coo")
    for i in range(2):
        for j in range(2):
            src, dst = bg.src_local[i, j], bg.dst_local[i, j]
            key = dst.astype(np.int64) * (part.n_c + 1) + src
            assert (np.diff(key) >= 0).all()  # destination-major, sentinels last
            f = rng.random((2, part.n_c)) < density
            un = rng.random((2, part.n_r)) < 0.5
            valid = (src < part.n_c) & (dst < part.n_r)
            want = np.full((2, part.n_r + 1), spmv_ref.INF, np.int64)
            for p in range(2):
                hit = valid & f[p][np.minimum(src, part.n_c - 1)]
                np.minimum.at(want[p], dst[hit], src[hit])
            want = want[:, : part.n_r]
            blk = backend.local_block(jnp.asarray(src), jnp.asarray(dst),
                                      jnp.asarray(bg.row_ptr[i, j]), (),
                                      part.n_r, part.n_c)
            push = backend.push_planes(blk, jnp.asarray(f))
            np.testing.assert_array_equal(np.asarray(push), want)
            pull = backend.pull_planes(blk, jnp.asarray(f), jnp.asarray(un))
            np.testing.assert_array_equal(
                np.asarray(pull), np.where(un, want, spmv_ref.INF)
            )
            deg = traversal.degree_vector(jnp.asarray(bg.row_ptr[i, j]))
            np.testing.assert_array_equal(
                np.asarray(deg), np.bincount(dst[valid], minlength=part.n_r)
            )
    # no sentinel tail: empty rows past the last edge stay empty
    tail_dst = jnp.asarray([0, 0], jnp.int32)
    blk = backend.local_block(jnp.asarray([2, 5], jnp.int32), tail_dst,
                              expand.row_offsets(tail_dst, 3), (), 3, 8)
    push = backend.push_planes(blk, jnp.ones((1, 8), bool))
    np.testing.assert_array_equal(np.asarray(push), [[2, spmv_ref.INF, spmv_ref.INF]])


def test_spmv_ops_pad_misaligned_shapes():
    """Blocks off any tile multiple (rows, degree and an unreached bitmap
    padded to the 1024-bit chunk) expand exactly through every entry point
    of the XLA ELL SpMV, single-plane and plane-batched."""
    n_rows, max_deg, n_cols = 1500, 9, 2048  # deliberately misaligned
    rng = np.random.default_rng(7)
    nbr = rng.integers(0, n_cols, size=(n_rows, max_deg)).astype(np.int32)
    nbr[rng.random((n_rows, max_deg)) < 0.3] = n_cols
    bits = rng.random(n_cols) < 0.2
    unreached = rng.random(n_rows) < 0.5
    f_words = bpref.pack(jnp.asarray(bits.astype(np.uint32)), 1)
    u_bits = np.zeros(2048, np.uint32)  # chunk-padded unreached bitmap
    u_bits[:n_rows] = unreached
    u_words = bpref.pack(jnp.asarray(u_bits), 1)
    expect = _python_spmv_oracle(nbr, bits, n_cols)
    out = spmv_ref.spmv_min(jnp.asarray(nbr), f_words, n_cols)
    assert out.shape == (n_rows,)
    np.testing.assert_array_equal(np.asarray(out), expect)
    out = spmv_ref.spmv_pull_min(jnp.asarray(nbr), f_words, u_words, n_cols)
    np.testing.assert_array_equal(
        np.asarray(out), np.where(unreached, expect, spmv_ref.INF)
    )
    outp = spmv_ref.spmv_min_planes(jnp.asarray(nbr), f_words[None], n_cols)
    assert outp.shape == (1, n_rows)
    np.testing.assert_array_equal(np.asarray(outp[0]), expect)
    outp = spmv_ref.spmv_pull_min_planes(
        jnp.asarray(nbr), f_words[None], u_words[None], n_cols
    )
    np.testing.assert_array_equal(
        np.asarray(outp[0]), np.where(unreached, expect, spmv_ref.INF)
    )


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1 << 16), root=st.integers(0, 299),
       skewed=st.booleans())
def test_single_device_backends_identical(seed, root, skewed):
    """hybrid == ell == coo parent AND level planes on the single-device
    driver, for every policy, on both uniform random and degree-skewed
    graphs (n off the 1024 alignment on purpose)."""
    rng = np.random.default_rng(seed)
    n = 300
    if skewed:
        m = int(rng.integers(1, 1500))
        # hub-heavy: half the endpoints land on a few vertices
        hubs = rng.integers(0, 4, size=(m, 2))
        rand = rng.integers(0, n, size=(m, 2))
        pick = rng.random((m, 2)) < 0.5
        edges = np.where(pick, hubs, rand)
    else:
        edges = rng.integers(0, n, size=(int(rng.integers(1, 1500)), 2))
    g = builder.build_csr(edges, n=n)
    src, dst = _device_graph(g)
    for policy in ("top_down", "bottom_up", "direction_opt"):
        base = bfs.bfs(src, dst, jnp.int32(root), g.n, policy=policy)
        for backend in ("ell", "hybrid"):
            res = bfs.bfs(src, dst, jnp.int32(root), g.n, policy=policy,
                          expand=backend)
            np.testing.assert_array_equal(
                np.asarray(res.parent), np.asarray(base.parent),
                err_msg=f"{policy}/{backend}",
            )
            np.testing.assert_array_equal(
                np.asarray(res.level), np.asarray(base.level),
                err_msg=f"{policy}/{backend}",
            )


def test_single_device_batched_backends_identical():
    g = builder.build_csr(kronecker.kronecker_edges(9, seed=5), n=1 << 9)
    src, dst = _device_graph(g)
    roots = bfs.hub_roots(g.degrees(), 3)
    base = bfs.bfs(src, dst, roots, g.n, policy="direction_opt")
    for backend in ("ell", "hybrid", "auto"):
        res = bfs.bfs(src, dst, roots, g.n, policy="direction_opt",
                      expand=backend)
        np.testing.assert_array_equal(np.asarray(res.parent), np.asarray(base.parent))
        np.testing.assert_array_equal(np.asarray(res.level), np.asarray(base.level))


def test_build_bfs_rejects_unknown_backend_and_bad_arity():
    import jax

    from repro.core import csr as csrmod, distributed_bfs as dbfs

    g = builder.build_csr(kronecker.kronecker_edges(8, seed=1), n=256)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    bg = csrmod.partition_2d(g, rows=1, cols=1)
    with pytest.raises(KeyError, match="expansion"):
        dbfs.build_bfs(mesh, bg, dbfs.DistBFSConfig(expand="csr5"))
    cfg = dbfs.DistBFSConfig(mode="raw", expand="hybrid")
    fn = dbfs.build_bfs(mesh, bg, cfg)
    blocks = dbfs.shard_blocked(mesh, bg, cfg)
    assert len(blocks) == 7  # src, dst, row offsets, slab, residue src/dst/offsets
    with pytest.raises(TypeError, match="shard_blocked"):
        fn(*blocks[:3], jnp.int32(0))  # COO arity with hybrid cfg


def _run(snippet: str, devices: int = 4, timeout: int = 1200) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", snippet],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


@pytest.mark.slow
def test_dist_backends_all_plans_policies_batched_4dev():
    """Tentpole acceptance: hybrid produces bit-identical parents/levels to
    coo across all 4 wire plans x 3 policies with batched roots on a
    hub-heavy Kronecker graph (ell rides along on the cheapest plan)."""
    out = _run(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.core import bfs as bfsmod, csr as csrmod, distributed_bfs as dbfs
from repro.graphgen import builder, kronecker
g = builder.build_csr(kronecker.kronecker_edges(9, seed=3), n=1 << 9)
mesh = jax.make_mesh((2, 2), ("data", "model"))
bg = csrmod.partition_2d(g, rows=2, cols=2)
roots = jnp.asarray(bfsmod.hub_roots(g.degrees(), 2).astype(np.int32))
for mode in ("raw", "bitmap", "auto", "btfly"):
    for pol in ("top_down", "bottom_up", "direction_opt"):
        outs = {}
        backends = ("coo", "hybrid", "ell") if mode == "raw" else ("coo", "hybrid")
        for backend in backends:
            cfg = dbfs.DistBFSConfig(mode=mode, policy=pol, expand=backend,
                                     alpha=0.01, beta=0.002)
            fn = dbfs.build_bfs(mesh, bg, cfg)
            blocks = dbfs.shard_blocked(mesh, bg, cfg)
            parent, level, depth = fn(*blocks, roots)
            outs[backend] = (np.asarray(parent), np.asarray(level))
        for backend in backends[1:]:
            np.testing.assert_array_equal(outs[backend][0], outs["coo"][0],
                                          err_msg=f"{mode}/{pol}/{backend}")
            np.testing.assert_array_equal(outs[backend][1], outs["coo"][1],
                                          err_msg=f"{mode}/{pol}/{backend}")
print("DIST BACKENDS ALL PLANS OK")
""",
        devices=4,
    )
    assert "DIST BACKENDS ALL PLANS OK" in out


@pytest.mark.slow
def test_dist_backends_equivalence_property_4dev():
    """Satellite acceptance: hypothesis property — hybrid == ell == coo on
    random degree-skewed and uniform graphs, every policy, C=2 grid."""
    out = _run(
        """
import os, sys
try:
    import hypothesis
except ImportError:
    sys.path.insert(0, os.path.join(r"%s", "tests", "_shims"))
from hypothesis import given, settings
from hypothesis import strategies as st
import numpy as np, jax, jax.numpy as jnp
from repro.core import csr as csrmod, distributed_bfs as dbfs
from repro.graphgen import builder
n = 1 << 9
mesh = jax.make_mesh((2, 2), ("data", "model"))

@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 1 << 16), root=st.integers(0, (1 << 9) - 1),
       skewed=st.booleans())
def prop(seed, root, skewed):
    rng = np.random.default_rng(seed)
    m = int(rng.integers(1, 800))
    if skewed:
        hubs = rng.integers(0, 3, size=(m, 2))
        rand = rng.integers(0, n, size=(m, 2))
        edges = np.where(rng.random((m, 2)) < 0.5, hubs, rand)
    else:
        edges = rng.integers(0, n, size=(m, 2))
    g = builder.build_csr(edges, n=n)
    bg = csrmod.partition_2d(g, rows=2, cols=2, e_cap_multiple=1024)
    outs = {}
    for backend in ("coo", "ell", "hybrid"):
        for pol in ("top_down", "bottom_up", "direction_opt"):
            cfg = dbfs.DistBFSConfig(mode="auto", policy=pol, expand=backend,
                                     alpha=0.01, beta=0.002)
            fn = dbfs.build_bfs(mesh, bg, cfg)
            blocks = dbfs.shard_blocked(mesh, bg, cfg)
            parent, level, depth = fn(*blocks, jnp.int32(root))
            outs[backend, pol] = (np.asarray(parent), np.asarray(level))
    for pol in ("top_down", "bottom_up", "direction_opt"):
        for backend in ("ell", "hybrid"):
            np.testing.assert_array_equal(outs[backend, pol][0], outs["coo", pol][0])
            np.testing.assert_array_equal(outs[backend, pol][1], outs["coo", pol][1])

prop()
print("BACKEND PROPERTY OK")
""" % REPO,
        devices=4,
        timeout=1800,
    )
    assert "BACKEND PROPERTY OK" in out


@pytest.mark.slow
def test_dist_backends_c3_grid_6dev():
    """Non-power-of-two C=3 grid (folded butterfly stages included):
    every backend matches the host oracle for every policy."""
    out = _run(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.core import csr as csrmod, distributed_bfs as dbfs, validate
from repro.graphgen import builder, kronecker
g = builder.build_csr(kronecker.kronecker_edges(9, seed=3), n=1 << 9)
mesh = jax.make_mesh((2, 3), ("data", "model"))
bg = csrmod.partition_2d(g, rows=2, cols=3)
ref = validate.reference_bfs(g, 0)
for mode in ("auto", "btfly"):
    for pol in ("top_down", "bottom_up", "direction_opt"):
        for backend in ("ell", "hybrid"):
            cfg = dbfs.DistBFSConfig(mode=mode, policy=pol, expand=backend,
                                     alpha=0.01, beta=0.002)
            fn = dbfs.build_bfs(mesh, bg, cfg)
            blocks = dbfs.shard_blocked(mesh, bg, cfg)
            parent, level, depth = fn(*blocks, jnp.int32(0))
            level = np.asarray(level)[:g.n]
            assert np.array_equal(level, ref), (mode, pol, backend)
            assert validate.validate_bfs_tree(g, np.asarray(parent)[:g.n], 0, level).ok
print("C3 BACKENDS OK")
""",
        devices=6,
    )
    assert "C3 BACKENDS OK" in out


@pytest.mark.slow
def test_commstats_and_hlo_invariant_across_backends_4dev():
    """Tentpole acceptance: expansion is compute-local — the CommStats
    ledger is byte-identical across backends (phase, fmt, collective,
    part, nbytes all equal), every ledger reconciles 1:1 with its lowered
    HLO, and the per-collective HLO byte totals are identical across
    backends for both the direct and the butterfly plan."""
    out = _run(
        """
import jax, jax.numpy as jnp
import numpy as np
from repro.comm import CommStats
from repro.core import csr as csrmod, distributed_bfs as dbfs
from repro.graphgen import builder, kronecker
from repro.launch import roofline
g = builder.build_csr(kronecker.kronecker_edges(9, seed=3), n=1 << 9)
mesh = jax.make_mesh((2, 2), ("data", "model"))
bg = csrmod.partition_2d(g, rows=2, cols=2)
roots = jax.ShapeDtypeStruct((2,), jnp.int32)
for mode in ("auto", "btfly"):
    ledgers, per_op = {}, {}
    for backend in ("coo", "ell", "hybrid"):
        cfg = dbfs.DistBFSConfig(mode=mode, policy="direction_opt", expand=backend)
        stats = CommStats()
        fn = dbfs.build_bfs(mesh, bg, cfg, stats=stats)
        blocks = dbfs.shard_blocked(mesh, bg, cfg)
        structs = [jax.ShapeDtypeStruct(b.shape, b.dtype) for b in blocks]
        compiled = jax.jit(fn).lower(*structs, roots).compile()
        cmp = roofline.compare_comm_stats(stats, compiled.as_text())
        assert cmp.match, (mode, backend, cmp.diff())
        ledgers[backend] = [
            (r.phase, r.fmt, r.collective, r.part, r.nbytes)
            for r in stats.records()
        ]
        per_op[backend] = cmp.per_phase
    assert ledgers["coo"] == ledgers["ell"] == ledgers["hybrid"], mode
    assert per_op["coo"] == per_op["ell"] == per_op["hybrid"], mode
print("BACKEND INVARIANCE OK")
""",
        devices=4,
    )
    assert "BACKEND INVARIANCE OK" in out
