"""Multi-source batch BFS: the B-plane axis across both drivers.

The contract: a batched run over ``roots (B,)`` produces, per plane,
parent/level arrays *identical* to B independent single-source runs — for
every traversal policy, every wire plan, and both drivers — while every
distributed exchange carries all B planes under ONE wire header and ONE
bucket consensus.  The ledger shows the split: payload collectives are
attributed per plane under ``{phase}@p{k}`` sub-zones that still reconcile
1:1 with the lowered HLO in aggregate, while the shared rounds (bucket
pmax, degree psum) stay whole under their base phase.
"""

import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.comm import formats
from repro.comm.ladder import BucketLadder
from repro.core import bfs, traversal
from repro.graphgen import builder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _device_graph(g):
    return jnp.asarray(g.src.astype(np.int32)), jnp.asarray(g.dst.astype(np.int32))


def test_validate_roots_errors():
    """Satellite: bad roots fail fast with a clear message instead of the
    silent wraparound indexing of the ``parent.at[root]`` scatter."""
    n = 64
    g = builder.build_csr(np.array([[0, 1], [1, 2]]), n=n)
    src, dst = _device_graph(g)
    with pytest.raises(TypeError, match="integer"):
        bfs.bfs(src, dst, jnp.float32(0), n)
    with pytest.raises(ValueError, match="out of range"):
        bfs.bfs(src, dst, jnp.int32(n), n)
    with pytest.raises(ValueError, match="out of range"):
        bfs.bfs(src, dst, np.array([1, -3]), n)
    with pytest.raises(ValueError, match="duplicate"):
        bfs.bfs(src, dst, np.array([5, 0, 5]), n)
    with pytest.raises(ValueError, match="scalar or"):
        bfs.bfs(src, dst, np.zeros((2, 2), np.int32), n)
    with pytest.raises(ValueError, match="at least one"):
        bfs.bfs(src, dst, np.zeros((0,), np.int32), n)
    # well-formed roots pass through as int32, values untouched
    assert bfs.validate_roots(np.int64(3), n).dtype == jnp.int32
    np.testing.assert_array_equal(bfs.validate_roots([3, 0, 63], n), [3, 0, 63])


def test_plane_meta_roundtrip_and_header_amortization():
    """B id streams share one packed meta word per plane: the sideband
    halves per source, and the plane wire strictly undercuts B separate
    single-plane wires; dense formats scale linearly (no header to share)."""
    counts = jnp.array([0, 5, 1 << 16], jnp.int32)  # counts reach cap == 2**16
    excs = jnp.array([0, 3, 1 << 13], jnp.int32)  # exceptions reach cap / 8
    c2, e2 = formats.unpack_plane_meta(formats.pack_plane_meta(counts, excs))
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(counts))
    np.testing.assert_array_equal(np.asarray(e2), np.asarray(excs))

    ladder = BucketLadder.default(8192, floor_words=8192, payload_width=16)
    stream = next(
        f for f in ladder.formats() if isinstance(f, formats.IdStreamFormat)
    )
    for b in (2, 4, 8):
        batched = formats.plane_wire_bytes(stream, b)
        assert batched == 4 * (b * stream.data_words + formats.plane_meta_words(b))
        assert batched < b * stream.wire_bytes
        assert batched / b < stream.wire_bytes  # strictly cheaper per source
    dense = formats.DenseFormat(8192)
    assert formats.plane_wire_bytes(dense, 4) == 4 * dense.wire_bytes


def test_oracle_anticipatory_mf_signal():
    """The Beamer m_f edge signal flips the direction one level before the
    vertex count crosses alpha*n: a hub entering the frontier blows up the
    frontier edge count while the popcount still reads sparse."""
    oracle = traversal.DensityOracle(1000, alpha=0.25, beta=0.05, alpha_mf=14.0)
    # popcount alone: 100 < alpha*n = 250 -> stay top-down
    assert not bool(oracle.next_direction(np.int32(100), False))
    # same popcount, but the frontier touches half the remaining edges
    assert bool(
        oracle.next_direction(np.int32(100), False, m_f=np.int32(500), m_u=np.int32(1000))
    )
    assert not bool(
        oracle.next_direction(np.int32(100), False, m_f=np.int32(10), m_u=np.int32(100000))
    )
    # elementwise over source planes: one plane enters on m_f, one on the
    # popcount, one stays put
    out = oracle.next_direction(
        np.array([100, 300, 100]),
        np.array([False, False, False]),
        m_f=np.array([500, 0, 0]),
        m_u=np.array([1000, 10**6, 10**6]),
    )
    np.testing.assert_array_equal(np.asarray(out), [True, True, False])
    # Beamer's C_TB growth guard: a shrinking tail frontier whose m_f
    # exceeds a collapsed m_u must NOT flap into the pull wire; the
    # popcount rule is unaffected by the guard
    out = oracle.next_direction(
        np.array([100, 300]),
        np.array([False, False]),
        m_f=np.array([500, 0]),
        m_u=np.array([1000, 10**6]),
        growing=np.array([False, False]),
    )
    np.testing.assert_array_equal(np.asarray(out), [False, True])
    assert bool(
        oracle.next_direction(np.int32(100), False, m_f=np.int32(500),
                              m_u=np.int32(1000), growing=np.bool_(True))
    )


def test_plane_counts_matches_per_plane_sums():
    rng = np.random.default_rng(0)
    for n in (3000, 4096):  # unaligned and aligned to the 1024-bit chunk
        oracle = traversal.DensityOracle(n)
        bits = rng.random((3, n)) < np.array([[0.0], [0.01], [0.6]])
        np.testing.assert_array_equal(
            np.asarray(oracle.plane_counts(jnp.asarray(bits))), bits.sum(axis=1)
        )


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1 << 16))
def test_batched_equals_singles_single_device(seed):
    """Property: bfs() with (B,) roots == B single-source runs, per plane,
    for every traversal policy; n_levels is the deepest plane's depth."""
    rng = np.random.default_rng(seed)
    n = 256
    m = int(rng.integers(1, 2048))
    edges = rng.integers(0, n, size=(m, 2))
    g = builder.build_csr(edges, n=n)
    src, dst = _device_graph(g)
    roots = rng.choice(n, size=3, replace=False).astype(np.int32)
    for policy in traversal.POLICIES:
        res_b = bfs.bfs(src, dst, jnp.asarray(roots), g.n, policy=policy)
        assert res_b.parent.shape == (3, g.n)
        depths = []
        for k, r in enumerate(roots):
            res_1 = bfs.bfs(src, dst, jnp.int32(int(r)), g.n, policy=policy)
            np.testing.assert_array_equal(
                np.asarray(res_b.parent)[k], np.asarray(res_1.parent),
                err_msg=f"{policy} root {r}",
            )
            np.testing.assert_array_equal(
                np.asarray(res_b.level)[k], np.asarray(res_1.level),
                err_msg=f"{policy} root {r}",
            )
            depths.append(int(res_1.n_levels))
        assert int(res_b.n_levels) == max(depths), (policy, depths)


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


_BATCH_EQUIV_SNIPPET = """
import os, sys
try:
    import hypothesis
except ImportError:
    sys.path.insert(0, os.path.join(r"%(repo)s", "tests", "_shims"))
from hypothesis import given, settings
from hypothesis import strategies as st
import numpy as np, jax, jax.numpy as jnp
from repro.core import csr as csrmod, distributed_bfs as dbfs
from repro.graphgen import builder
n = 1 << 10
ROWS, COLS, B = 2, %(cols)d, 3
mesh = jax.make_mesh((ROWS, COLS), ("data", "model"))
g0 = builder.build_csr(np.array([[0, 1]]), n=n)
part = csrmod.partition_2d(g0, rows=ROWS, cols=COLS, e_cap_multiple=1024).part
fns = {}
for mode in ("raw", "bitmap", "auto", "btfly"):
    for pol in ("top_down", "bottom_up", "direction_opt"):
        cfg = dbfs.DistBFSConfig(mode=mode, policy=pol, alpha=0.01, beta=0.002)
        fns[mode, pol] = (dbfs.build_bfs(mesh, part, cfg), cfg)

@settings(max_examples=%(examples)d, deadline=None)
@given(seed=st.integers(0, 1 << 16))
def prop(seed):
    rng = np.random.default_rng(seed)
    m = int(rng.integers(1, 400))
    edges = rng.integers(0, n, size=(m, 2))
    g = builder.build_csr(edges, n=n)
    bg = csrmod.partition_2d(g, rows=ROWS, cols=COLS, e_cap_multiple=1024)
    assert bg.e_cap == 1024  # pinned -> the compiled fns are reused
    roots = rng.choice(n, size=B, replace=False).astype(np.int32)
    for (mode, pol), (fn, cfg) in fns.items():
        blocks = dbfs.shard_blocked(mesh, bg, cfg)
        pb, lb, db = fn(*blocks, jnp.asarray(roots))
        pb, lb = np.asarray(pb), np.asarray(lb)
        assert pb.shape[0] == B
        for k, r in enumerate(roots):
            p1, l1, d1 = fn(*blocks, jnp.int32(int(r)))
            np.testing.assert_array_equal(
                pb[k], np.asarray(p1), err_msg=f"{mode}/{pol}/root {r}")
            np.testing.assert_array_equal(
                lb[k], np.asarray(l1), err_msg=f"{mode}/{pol}/root {r}")

prop()
# root validation rides the same wrapper in the distributed driver
fn, cfg = fns["raw", "top_down"]
blocks = dbfs.shard_blocked(
    mesh, csrmod.partition_2d(g0, rows=ROWS, cols=COLS, e_cap_multiple=1024), cfg)
for bad in (np.array([1, 1], np.int32), np.array([n], np.int32)):
    try:
        fn(*blocks, bad)
        raise SystemExit(f"no error for roots {bad}")
    except ValueError:
        pass
print("BATCH EQUIV OK")
"""


@pytest.mark.slow
def test_batched_equals_singles_all_plans_4dev():
    """Satellite acceptance: batched distributed BFS equals B independent
    single-source runs for all 4 wire plans x 3 policies on the C=2 grid
    (hypothesis drives the graphs; low alpha forces direction_opt through
    its bottom-up branch so both wires carry real planes)."""
    out = _run(
        _BATCH_EQUIV_SNIPPET % {"repo": REPO, "cols": 2, "examples": 5},
        devices=4,
    )
    assert "BATCH EQUIV OK" in out


@pytest.mark.slow
def test_batched_equals_singles_c3_6dev():
    """Same property on the C=3 grid: the batched planes ride the butterfly
    fold/unfold stages and the non-power-of-two alltoall geometry."""
    out = _run(
        _BATCH_EQUIV_SNIPPET % {"repo": REPO, "cols": 3, "examples": 3},
        devices=6,
    )
    assert "BATCH EQUIV OK" in out


@pytest.mark.slow
def test_per_plane_comm_stats_match_hlo_4dev():
    """Tentpole acceptance: at B=3 the CommStats ledger reconciles 1:1 with
    the lowered HLO for all 4 plans x 3 policies, every plane-carrying zone
    splits into exactly B ``@p{k}`` sub-zones, and the shared rounds — the
    bucket pmax consensus and the degree psum — are never split (ONE round
    serves all planes: the amortization the ledger must show)."""
    out = _run(
        """
import jax, jax.numpy as jnp
from repro.comm import CommStats
from repro.core import csr as csrmod, distributed_bfs as dbfs
from repro.launch import roofline
B = 3
part = csrmod.Partition2D(n=1 << 14, n_orig=1 << 14, rows=2, cols=2)
mesh = jax.make_mesh((2, 2), ("data", "model"))
blk = jax.ShapeDtypeStruct((2, 2, 1024), jnp.int32)
ptr = jax.ShapeDtypeStruct((2, 2, part.n_r + 1), jnp.int32)
for mode in ("raw", "bitmap", "auto", "btfly"):
    stage = (lambda z: z + "[btfly:0]") if mode == "btfly" else (lambda z: z)
    for pol in ("top_down", "bottom_up", "direction_opt"):
        stats = CommStats()
        fn = dbfs.build_bfs(
            mesh, part, dbfs.DistBFSConfig(mode=mode, policy=pol), stats=stats
        )
        compiled = jax.jit(fn).lower(
            blk, blk, ptr, jax.ShapeDtypeStruct((B,), jnp.int32)
        ).compile()
        cmp = roofline.compare_comm_stats(stats, compiled.as_text())
        assert cmp.match, (mode, pol, cmp.diff())
        planes, bare = {}, set()
        for z in cmp.per_phase:
            if "@p" in z:
                base, _, k = z.partition("@p")
                planes.setdefault(base, set()).add(int(k))
            else:
                bare.add(z)
        want = {"bfs/column", "bfs/transpose", "bfs/termination"}
        if pol in ("top_down", "direction_opt"):
            want |= {stage("bfs/row")}
        if pol in ("bottom_up", "direction_opt"):
            want |= {stage("bfs/row-pull"), stage("bfs/unreached")}
        assert set(planes) == want, (mode, pol, sorted(planes))
        assert all(ks == set(range(B)) for ks in planes.values()), (mode, pol, planes)
        assert "bfs/degree" not in planes
        assert ("bfs/degree" in bare) == (pol == "direction_opt"), (mode, pol, bare)
        # any other whole-phase entry is a consensus rider on a plane zone
        assert bare - {"bfs/degree"} <= want, (mode, pol, sorted(bare))
print("PLANE PARITY OK")
""",
        devices=4,
    )
    assert "PLANE PARITY OK" in out
