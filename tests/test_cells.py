"""Cell-builder integration: every (arch x shape) cell lowers coherently.

Full compiles for the production meshes happen in launch/dryrun.py (and its
artifacts are checked into experiments/); here every cell is *lowered* on a
small forced-device mesh in a subprocess — catching shape/sharding drift in
CI without the 512-device compile cost — plus one full dryrun.run_cell
execution end to end.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(snippet: str, devices: int, timeout: int = 1800) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", snippet], env=env, capture_output=True, text=True,
        timeout=timeout,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout[-2000:]}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


@pytest.mark.slow
def test_all_cells_lower_on_small_mesh():
    out = _run(
        """
import jax
from jax.sharding import Mesh
from repro.launch import cells
mesh = jax.make_mesh((2, 2), ("data", "model"))
n_ok = n_skip = 0
for arch, shape in cells.all_cells():
    cell = cells.build_cell(arch, shape, mesh)
    if cell.kind == "skip":
        n_skip += 1
        continue
    jax.jit(cell.fn, in_shardings=cell.in_shardings).lower(*cell.args)
    assert cell.meta["model_flops"] > 0, (arch, shape)
    n_ok += 1
print(f"LOWERED {n_ok} cells, {n_skip} skips")
assert n_skip == 5  # long_500k x 5 LM archs
assert n_ok + n_skip == len(cells.all_cells())
""",
        devices=4,
    )
    assert "LOWERED 38 cells, 5 skips" in out  # 10 archs x 4 + graph500 x 3 - 5


@pytest.mark.slow
def test_perf_variants_lower():
    """The §Perf variant knobs still produce lowerable cells."""
    out = _run(
        """
import jax
from repro.launch import cells
mesh = jax.make_mesh((2, 2), ("data", "model"))
for arch, shape, variant in [
    ("deepseek-v2-236b", "train_4k", "bf16-fullremat-moepin-experttp"),
    ("gemma-2b", "decode_32k", "tpserve"),
    ("autoint", "serve_bulk", "modeltable-int8table"),
    ("graph500", "scale30", "ecap15-bitmaponly"),
]:
    cell = cells.build_cell(arch, shape, mesh, variant=variant)
    with jax.set_mesh(mesh):  # bare-P sharding constraints need a mesh
        jax.jit(cell.fn, in_shardings=cell.in_shardings).lower(*cell.args)
print("VARIANTS OK")
""",
        devices=4,
    )
    assert "VARIANTS OK" in out


@pytest.mark.slow
def test_dryrun_driver_end_to_end(tmp_path):
    """dryrun.run_cell on the real 512-device mesh, one light cell."""
    out = _run(
        f"""
import repro.launch.dryrun as d
rec = d.run_cell("gemma-2b", "prefill_32k", multi_pod=True, out_dir=r"{tmp_path}")
assert rec["status"] == "ok", rec.get("error")
assert rec["roofline"]["collective_bytes"] > 0
assert rec["memory"]["temp_bytes"] > 0
rec2 = d.run_cell("minicpm-2b", "long_500k", multi_pod=False, out_dir=r"{tmp_path}")
assert rec2["status"] == "skip" and "sub-quadratic" in rec2["skip_reason"]
print("DRYRUN DRIVER OK", rec["roofline"]["dominant"])
""",
        devices=1,  # dryrun module forces 512 itself before importing jax
    )
    assert "DRYRUN DRIVER OK" in out
