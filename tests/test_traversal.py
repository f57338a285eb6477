"""Direction-optimizing traversal policies: equivalence + oracle + wires.

The contract: ``bottom_up`` and ``direction_opt`` produce level-identical
(and parent-identical) results to ``top_down`` on arbitrary graphs, across
every wire mode — the directions differ in probe representation and wire
shape only.  The density oracle's popcount equals the plain frontier sum,
and its hysteresis band behaves.
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
from repro.core import bfs, traversal, validate
from repro.graphgen import builder, kronecker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALT_POLICIES = ("bottom_up", "direction_opt")


def _device_graph(g):
    return jnp.asarray(g.src.astype(np.int32)), jnp.asarray(g.dst.astype(np.int32))


def test_policies_registered():
    assert set(wire_registry.available_traversals()) >= set(traversal.POLICIES)
    with pytest.raises(KeyError):
        wire_registry.traversal("sideways")


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1 << 16), root=st.integers(0, 255))
def test_policies_level_identical_random_graphs(seed, root):
    """bottom_up and direction_opt reproduce top_down's parent AND level
    arrays exactly on arbitrary random graphs."""
    rng = np.random.default_rng(seed)
    n = 256
    m = rng.integers(1, 2048)
    edges = rng.integers(0, n, size=(m, 2))
    g = builder.build_csr(edges, n=n)
    src, dst = _device_graph(g)
    base = bfs.bfs(src, dst, jnp.int32(root), g.n, policy="top_down")
    ref = validate.reference_bfs(g, root)
    np.testing.assert_array_equal(np.asarray(base.level), ref)
    for policy in ALT_POLICIES:
        res = bfs.bfs(src, dst, jnp.int32(root), g.n, policy=policy)
        np.testing.assert_array_equal(np.asarray(res.parent), np.asarray(base.parent))
        np.testing.assert_array_equal(np.asarray(res.level), np.asarray(base.level))
        assert int(res.n_levels) == int(base.n_levels)
        v = validate.validate_bfs_tree(g, np.asarray(res.parent), root, np.asarray(res.level))
        assert v.ok, (policy, v.failures)


@pytest.mark.parametrize("policy", ALT_POLICIES)
def test_bfs_levels_policy_sizes(policy):
    g = builder.build_csr(kronecker.kronecker_edges(8, seed=1), n=256)
    src, dst = _device_graph(g)
    res, sizes = bfs.bfs_levels(src, dst, jnp.int32(0), g.n, max_levels=16, policy=policy)
    n_reached = int((np.asarray(res.level) >= 0).sum())
    assert int(np.asarray(sizes).sum()) + 1 == n_reached


def test_oracle_popcount_matches_sum():
    rng = np.random.default_rng(0)
    # 3000: not a 1024-bit multiple; 33*1024: packed words not a multiple of
    # the popcount kernel's 1024-word block (regression: fallback reshape)
    for n in (3000, 33 * 1024):
        oracle = traversal.DensityOracle(n)
        for density in (0.0, 0.01, 0.5, 1.0):
            bits = jnp.asarray(rng.random(n) < density)
            planes = jnp.stack([bits, ~bits])
            np.testing.assert_array_equal(
                np.asarray(oracle.plane_counts(planes)),
                [int(np.asarray(bits).sum()), n - int(np.asarray(bits).sum())],
            )


def test_oracle_hysteresis():
    oracle = traversal.DensityOracle(1000, alpha=0.25, beta=0.05)
    # below alpha from top-down: stay top-down
    assert not bool(oracle.next_direction(np.int32(250), False))
    assert bool(oracle.next_direction(np.int32(251), False))
    # inside the hysteresis band from bottom-up: stay bottom-up
    assert bool(oracle.next_direction(np.int32(100), True))
    assert not bool(oracle.next_direction(np.int32(49), True))


def test_ladder_alpha_matches_row_ladder_edge():
    from repro.comm.ladder import BucketLadder

    s, wp = 8192, 16
    ladder = BucketLadder.default(s, floor_words=s, payload_width=wp)
    assert ladder.specs  # sparse buckets exist at this geometry
    assert traversal.ladder_alpha(s, wp) == ladder.specs[-1].cap / s


def test_direction_opt_beats_top_down_on_dense_level_bench():
    """Acceptance: on the scale-15 2x2 bench, direction_opt selects
    bottom-up on at least one dense level and moves fewer row-phase wire
    bytes there than top_down's ALLTOALLV (the BENCH_comm.json policy
    dimension); the butterfly plan's staged volumes reconcile with the
    static byte model (the same check CI runs via
    scripts/check_bench_comm.py)."""
    import importlib.util

    from benchmarks import bfs_comm

    table, levels = bfs_comm.run(scale=15, rows=2, cols=2)
    td = {d["level"]: d for d in levels["top_down"]}
    bu = [d for d in levels["direction_opt"] if d["direction"] == "bottom_up"]
    assert bu, "direction_opt never selected bottom-up"
    assert any(d["density"] > 0.25 for d in bu)  # a genuinely dense level
    assert any(
        d["row_bytes_packed"] < td[d["level"]]["row_bytes_packed"] for d in bu
    ), (bu, td)
    # the policy AND plan dimensions are present in the table
    pols = {r["policy"] for r in table}
    assert pols == set(traversal.POLICIES)
    assert {r["plan"] for r in table} == set(bfs_comm.PLANS)
    # staged butterfly volumes vs the WirePlan static byte model — exercise
    # the CI checker itself on an in-memory BENCH document
    spec = importlib.util.spec_from_file_location(
        "check_bench_comm", os.path.join(REPO, "scripts", "check_bench_comm.py")
    )
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)
    n = 1 << 15  # scale 15 on 2x2 needs no extra padding (8 x 4096-chunks)
    doc = {"chunk": n // 4, "n": n, "policy_levels": levels, "table": table}
    assert checker.check(doc) > 0


def test_btfly_schedule_and_byte_model():
    """Stage schedule invariants at every grid width, and the static byte
    model knows every format a stage can choose."""
    from repro.comm import butterfly

    for c in range(1, 9):
        sched = butterfly.ButterflySchedule(c)
        assert sched.p & (sched.p - 1) == 0 and sched.p <= c < 2 * sched.p
        assert sched.extra == c - sched.p
        assert sched.slots == (2 if sched.extra else 1)
        assert 1 << sched.n_stages == sched.p
        # each stage is a pairwise swap of the power-of-two ranks
        for t in range(sched.n_stages):
            perm = sched.stage_perm(t)
            assert sorted(src for src, _ in perm) == list(range(sched.p))
            assert all(dst == src ^ (1 << t) for src, dst in perm)
        # total leaf rows exchanged over all stages = p - 1 (halving series)
        assert sum(sched.stage_blocks(t) for t in range(sched.n_stages)) == sched.p - 1
        # every row chunk maps to exactly one leaf
        leaves = {sched.leaf_of_chunk(q) for q in range(c)}
        assert len(leaves) == c
    s, n = 8192, 1 << 15
    ladder, floor = butterfly.row_wire(s, n)
    assert floor.name == "bitmap+p16"  # 15-bit global ids pack at class 16
    for fmt in ladder.formats():
        assert butterfly.stage_unit_bytes(s, n, fmt.name) == fmt.wire_bytes
    assert butterfly.stage_unit_bytes(s, n, floor.name) == floor.wire_bytes
    assert butterfly.stage_unit_bytes(s, n, "bitmap", zone="unreached") == 4 * (s // 32)
    # the same pfor name prices differently on the two wires (payload)
    col_ladder, _ = butterfly.unreached_wire(1 << 16)
    row_ladder, _ = butterfly.row_wire(1 << 16, 1 << 18)
    shared = {f.name for f in col_ladder.formats()} & {
        f.name for f in row_ladder.formats()
    }
    assert shared and all(
        butterfly.stage_unit_bytes(1 << 16, 1 << 18, nm, zone="row")
        > butterfly.stage_unit_bytes(1 << 16, 1 << 18, nm, zone="unreached")
        for nm in shared
    )
    with pytest.raises(KeyError):
        butterfly.stage_unit_bytes(s, n, "no-such-format")
    # at 32-bit global ids the floor degenerates to the dense vector
    _, floor32 = butterfly.row_wire(8192, 1 << 20)
    assert floor32.name == "dense-i32"


def _run(snippet: str, devices: int = 4, timeout: int = 900) -> str:
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
def test_distributed_policies_all_modes_4dev():
    """Every policy x wire-mode combination matches the host oracle; a low
    alpha forces direction_opt through its bottom-up branch for real."""
    out = _run(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.core import csr as csrmod, distributed_bfs as dbfs, validate
from repro.graphgen import builder, kronecker
g = builder.build_csr(kronecker.kronecker_edges(10, seed=3), n=1<<10)
mesh = jax.make_mesh((2, 2), ("data", "model"))
bg = csrmod.partition_2d(g, rows=2, cols=2)
ref = validate.reference_bfs(g, 0)
for mode in ("raw", "bitmap", "auto", "btfly"):
    for pol in ("top_down", "bottom_up", "direction_opt"):
        cfg = dbfs.DistBFSConfig(mode=mode, policy=pol, alpha=0.01, beta=0.002)
        fn = dbfs.build_bfs(mesh, bg, cfg)
        blocks = dbfs.shard_blocked(mesh, bg, cfg)
        parent, level, depth = fn(*blocks, jnp.int32(0))
        level = np.asarray(level)[:g.n]
        assert np.array_equal(level, ref), (mode, pol)
        assert validate.validate_bfs_tree(g, np.asarray(parent)[:g.n], 0, level).ok
print("DIST POLICIES OK")
""",
        devices=4,
    )
    assert "DIST POLICIES OK" in out


@pytest.mark.slow
def test_comm_stats_match_hlo_btfly_4dev():
    """Tentpole acceptance: every butterfly stage's CommStats entries
    reconcile 1:1 with the collective-permute ops in the lowered HLO, for
    all three policies, on both a 1-stage (C=2) and a 2-stage (C=4) grid;
    the transpose zone's moved bytes undercut its HLO bytes (identity
    ppermute pairs are not wire traffic)."""
    out = _run(
        """
import jax, jax.numpy as jnp
from repro.comm import CommStats
from repro.core import csr as csrmod, distributed_bfs as dbfs
from repro.launch import roofline
for rows, cols, mesh_shape in ((2, 2, (2, 2)), (2, 4, (2, 4))):
    part = csrmod.Partition2D(n=1 << 16, n_orig=1 << 16, rows=rows, cols=cols)
    mesh = jax.make_mesh(mesh_shape, ("data", "model"))
    blk = jax.ShapeDtypeStruct((rows, cols, 4096), jnp.int32)
    ptr = jax.ShapeDtypeStruct((rows, cols, part.n_r + 1), jnp.int32)
    n_stages = cols.bit_length() - 1
    for pol in ("top_down", "bottom_up", "direction_opt"):
        stats = CommStats()
        fn = dbfs.build_bfs(mesh, part, dbfs.DistBFSConfig(mode="btfly", policy=pol), stats=stats)
        compiled = jax.jit(fn).lower(blk, blk, ptr, jax.ShapeDtypeStruct((), jnp.int32)).compile()
        cmp = roofline.compare_comm_stats(stats, compiled.as_text())
        assert cmp.match, (cols, pol, cmp.diff())
        stages = {f"bfs/row[btfly:{t}]" for t in range(n_stages)}
        want = {"bfs/column", "bfs/transpose", "bfs/termination"}
        if pol == "top_down":
            want |= stages
        elif pol == "bottom_up":
            want |= {z.replace("row[", "row-pull[") for z in stages}
            want |= {f"bfs/unreached[btfly:{t}]" for t in range(n_stages)}
        else:
            want |= stages | {z.replace("row[", "row-pull[") for z in stages}
            want |= {f"bfs/unreached[btfly:{t}]" for t in range(n_stages)}
            want |= {"bfs/degree"}  # anticipatory m_f oracle's one-time psum
        assert set(cmp.per_phase) == want, (cols, pol, sorted(cmp.per_phase))
        moved = stats.per_phase_moved()
        assert moved["bfs/transpose"] < cmp.per_phase["bfs/transpose"]
print("BTFLY COMM STATS MATCH OK")
""",
        devices=8,
    )
    assert "BTFLY COMM STATS MATCH OK" in out


@pytest.mark.slow
def test_btfly_folded_non_power_of_two_6dev():
    """C=3 exercises the folded first stage: the overhang rank's candidates
    fold onto rank 0 before the butterfly and unfold after; results match
    the host oracle for every policy, and the fold/unfold CommStats zones
    reconcile with the HLO."""
    out = _run(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.comm import CommStats
from repro.core import csr as csrmod, distributed_bfs as dbfs, validate
from repro.graphgen import builder, kronecker
from repro.launch import roofline
g = builder.build_csr(kronecker.kronecker_edges(10, seed=3), n=1<<10)
mesh = jax.make_mesh((2, 3), ("data", "model"))
bg = csrmod.partition_2d(g, rows=2, cols=3)
ref = validate.reference_bfs(g, 0)
for pol in ("top_down", "bottom_up", "direction_opt"):
    cfg = dbfs.DistBFSConfig(mode="btfly", policy=pol, alpha=0.01, beta=0.002)
    stats = CommStats()
    fn = dbfs.build_bfs(mesh, bg, cfg, stats=stats)
    blocks = dbfs.shard_blocked(mesh, bg, cfg)
    lowered = jax.jit(fn).lower(*blocks, jnp.int32(0)).compile()
    cmp = roofline.compare_comm_stats(stats, lowered.as_text())
    assert cmp.match, (pol, cmp.diff())
    row_zone = "bfs/row-pull" if pol == "bottom_up" else "bfs/row"
    assert f"{row_zone}[btfly:fold]" in cmp.per_phase, sorted(cmp.per_phase)
    assert f"{row_zone}[btfly:unfold]" in cmp.per_phase
    parent, level, depth = fn(*blocks, jnp.int32(0))
    level = np.asarray(level)[:g.n]
    assert np.array_equal(level, ref), (pol, np.nonzero(level != ref)[0][:10])
    assert validate.validate_bfs_tree(g, np.asarray(parent)[:g.n], 0, level).ok
print("BTFLY FOLD OK")
""",
        devices=6,
    )
    assert "BTFLY FOLD OK" in out


@pytest.mark.slow
def test_btfly_equals_raw_property_4dev():
    """Satellite acceptance: property test — the btfly plan produces
    parents AND levels identical to mode 'raw' for every policy on random
    graphs (hypothesis drives the graphs; the compiled fns are reused
    across examples because shapes are pinned)."""
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
n = 1 << 10
mesh = jax.make_mesh((2, 2), ("data", "model"))
fns = {}
for mode in ("raw", "btfly"):
    for pol in ("top_down", "bottom_up", "direction_opt"):
        cfg = dbfs.DistBFSConfig(mode=mode, policy=pol, alpha=0.01, beta=0.002)
        part = csrmod.Partition2D(n=4096, n_orig=n, rows=2, cols=2)
        fns[mode, pol] = (dbfs.build_bfs(mesh, part, cfg), cfg)

@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 1 << 16), root=st.integers(0, (1 << 10) - 1))
def prop(seed, root):
    rng = np.random.default_rng(seed)
    m = int(rng.integers(1, 400))
    edges = rng.integers(0, n, size=(m, 2))
    g = builder.build_csr(edges, n=n)
    bg = csrmod.partition_2d(g, rows=2, cols=2, e_cap_multiple=1024)
    assert bg.e_cap == 1024  # pinned -> compiled fns are reused
    outs = {}
    for (mode, pol), (fn, cfg) in fns.items():
        blocks = dbfs.shard_blocked(mesh, bg, cfg)
        parent, level, depth = fn(*blocks, jnp.int32(root))
        outs[mode, pol] = (np.asarray(parent), np.asarray(level))
    for pol in ("top_down", "bottom_up", "direction_opt"):
        np.testing.assert_array_equal(outs["btfly", pol][0], outs["raw", pol][0])
        np.testing.assert_array_equal(outs["btfly", pol][1], outs["raw", pol][1])

prop()
print("BTFLY PROPERTY OK")
""" % REPO,
        devices=4,
        timeout=1200,
    )
    assert "BTFLY PROPERTY OK" in out


@pytest.mark.slow
def test_row_payload_localization_8dev():
    """Regression: at C=4 with n_c=2**15 the packed-parent class (16 bits)
    is narrower than global ids (17 bits) — the sparse push row branch used
    to truncate the high bit.  Payloads now travel column-local and are
    re-globalized from the all-to-all row index."""
    out = _run(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.core import csr as csrmod, distributed_bfs as dbfs
from repro.graphgen import builder
edges = np.array([[0, 70000], [70000, 100]])
g = builder.build_csr(edges, n=1 << 17)
mesh = jax.make_mesh((2, 4), ("data", "model"))
bg = csrmod.partition_2d(g, rows=2, cols=4)
for mode in ("auto", "btfly"):
    cfg = dbfs.DistBFSConfig(mode=mode, policy="top_down")
    fn = dbfs.build_bfs(mesh, bg, cfg)
    blocks = dbfs.shard_blocked(mesh, bg, cfg)
    parent, level, depth = fn(*blocks, jnp.int32(0))
    parent = np.asarray(parent)
    assert parent[100] == 70000, (mode, parent[100])
    assert parent[70000] == 0, (mode, parent[70000])
print("PAYLOAD LOCALIZATION OK")
""",
        devices=8,
    )
    assert "PAYLOAD LOCALIZATION OK" in out


@pytest.mark.slow
def test_comm_stats_match_hlo_bottom_up_4dev():
    """Satellite acceptance: the CommStats ledger still matches the lowered
    HLO per op kind for the bottom-up exchanges (found-bitmap row phase +
    unreached all-gather), in every wire mode, for both pull policies."""
    out = _run(
        """
import jax, jax.numpy as jnp
from repro.comm import CommStats
from repro.core import csr as csrmod, distributed_bfs as dbfs
from repro.launch import roofline
part = csrmod.Partition2D(n=1 << 16, n_orig=1 << 16, rows=2, cols=2)
mesh = jax.make_mesh((2, 2), ("data", "model"))
blk = jax.ShapeDtypeStruct((2, 2, 4096), jnp.int32)
ptr = jax.ShapeDtypeStruct((2, 2, part.n_r + 1), jnp.int32)
for mode in ("raw", "bitmap", "auto"):
    for pol in ("bottom_up", "direction_opt"):
        stats = CommStats()
        fn = dbfs.build_bfs(mesh, part, dbfs.DistBFSConfig(mode=mode, policy=pol), stats=stats)
        compiled = jax.jit(fn).lower(blk, blk, ptr, jax.ShapeDtypeStruct((), jnp.int32)).compile()
        cmp = roofline.compare_comm_stats(stats, compiled.as_text())
        assert cmp.match, (mode, pol, cmp.diff())
        want = {"bfs/column", "bfs/row-pull", "bfs/transpose", "bfs/termination", "bfs/unreached"}
        if pol == "direction_opt":
            want |= {"bfs/row", "bfs/degree"}
        assert set(cmp.per_phase) == want, (mode, pol, cmp.per_phase)
print("BU COMM STATS MATCH OK")
""",
        devices=4,
    )
    assert "BU COMM STATS MATCH OK" in out
