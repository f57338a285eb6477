"""Roofline extraction: HLO collective parsing + term arithmetic."""

import os
import subprocess
import sys

import pytest

from repro.launch import roofline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_FAKE_HLO = """\
HloModule test

%while_body.7 (p: (f32[128,256])) -> (f32[128,256]) {
  %arg = f32[128,256] parameter(0)
  %ag = f32[512,256] all-gather(%arg), dimensions={0}
  %ar = f32[128,256] all-reduce(%arg), to_apply=%add
  ROOT %t = (f32[128,256]) tuple(%ar)
}

ENTRY %main (p0: f32[128,256]) -> f32[128,256] {
  %p0 = f32[128,256] parameter(0)
  %w = (f32[128,256]) while((f32[128,256]) %tup), condition=%cond.1, body=%while_body.7
  %cp = bf16[64,64] collective-permute(%x), source_target_pairs={{0,1}}
  ROOT %out = f32[128,256] get-tuple-element(%w), index=0
}
"""


def test_shape_bytes():
    assert roofline._shape_bytes("f32[128,256]") == 128 * 256 * 4
    assert roofline._shape_bytes("bf16[8]") == 16
    assert roofline._shape_bytes("(f32[2,2], s8[4])") == 20
    assert roofline._shape_bytes("pred[]") == 1  # scalar = one element


def test_parse_collectives_loop_scaling():
    stats1 = roofline.parse_collectives(_FAKE_HLO, loop_mult=1.0)
    ag = 512 * 256 * 4
    ar = 128 * 256 * 4 * 2  # all-reduce counts 2x
    cp = 64 * 64 * 2
    assert stats1.per_op["all-gather"] == ag
    assert stats1.per_op["all-reduce"] == ar
    assert stats1.per_op["collective-permute"] == cp
    # ops inside the while body scale by the trip count; top-level ops don't
    stats10 = roofline.parse_collectives(_FAKE_HLO, loop_mult=10.0)
    assert stats10.per_op["all-gather"] == 10 * ag
    assert stats10.per_op["all-reduce"] == 10 * ar
    assert stats10.per_op["collective-permute"] == cp


def test_roofline_terms_arithmetic():
    t = roofline.RooflineTerms(
        compute_s=1.0, memory_s=2.0, collective_s=0.5,
        hlo_flops=1e12, hlo_bytes=1e12, collective_bytes=1e10,
        model_flops=roofline.PEAK_FLOPS * 256,  # 1s of ideal all-chip compute
        chips=256,
    )
    assert t.dominant == "memory"
    assert t.bound_s == 2.0
    assert abs(t.roofline_fraction - 0.5) < 1e-9  # 1s ideal / 2s bound


@pytest.mark.slow
def test_parse_real_compiled_program():
    """Collectives of a real SPMD-compiled psum program are found."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    snippet = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch import roofline
mesh = jax.make_mesh((4,), ("data",))
def f(x):
    return jax.lax.psum(x * 2, "data")
m = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False)
compiled = jax.jit(m).lower(jax.ShapeDtypeStruct((1024,), jnp.float32)).compile()
stats = roofline.parse_collectives(compiled.as_text())
assert stats.n_ops >= 1, compiled.as_text()[:500]
assert stats.per_op.get("all-reduce", 0) > 0
print("REAL HLO PARSE OK", stats.per_op)
"""
    out = subprocess.run([sys.executable, "-c", snippet], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "REAL HLO PARSE OK" in out.stdout


@pytest.mark.slow
def test_comm_stats_match_hlo_all_modes():
    """Acceptance: the CommStats ledger every collective reports through
    agrees per op kind with the collective operand bytes parsed out of the
    compiled HLO, for the raw / bitmap / auto wire plans (the auto row
    ladder has sparse buckets at s=16384, so the lax.switch branches are
    in the HLO and in the ledger)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    snippet = """
import jax, jax.numpy as jnp
from repro.comm import CommStats
from repro.core import csr as csrmod, distributed_bfs as dbfs
from repro.launch import roofline
part = csrmod.Partition2D(n=1 << 16, n_orig=1 << 16, rows=2, cols=2)
mesh = jax.make_mesh((2, 2), ("data", "model"))
blk = jax.ShapeDtypeStruct((2, 2, 4096), jnp.int32)
ptr = jax.ShapeDtypeStruct((2, 2, part.n_r + 1), jnp.int32)
for mode in ("raw", "bitmap", "auto"):
    stats = CommStats()
    fn = dbfs.build_bfs(mesh, part, dbfs.DistBFSConfig(mode=mode), stats=stats)
    compiled = jax.jit(fn).lower(blk, blk, ptr, jax.ShapeDtypeStruct((), jnp.int32)).compile()
    cmp = roofline.compare_comm_stats(stats, compiled.as_text())
    assert cmp.match, (mode, cmp.diff())
    # every BFS exchange zone is in the ledger
    assert set(cmp.per_phase) == {"bfs/column", "bfs/row", "bfs/transpose", "bfs/termination"}, cmp.per_phase
print("COMM STATS MATCH OK")
"""
    out = subprocess.run([sys.executable, "-c", snippet], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "COMM STATS MATCH OK" in out.stdout
