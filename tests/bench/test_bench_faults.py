"""The comparison that decides ``correct`` calls broken runs wrong.

A whole run of a cell at a small size on the CPU, past the harness's look
for a chip, with the timed path broken underneath: a level step that
returns its state unchanged, an answer altered where it is produced, and
(on the 2x2 grid, with four host devices) the exchange between chips left
out.  Traffic of one root per call has no batch to halve.  The control,
the reference stopping one level early, has to fail too."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp

from bench import cell, control
from repro.core import algebra

REPO = Path(__file__).resolve().parents[2]


def _run(root, workload="g500-s22-1x1.bfs", seed=11):
    return cell.run(root, workload, seed, 0.2, False, time.perf_counter(),
                    log=lambda m: None)


def test_sound_run_is_correct(tiny_root):
    result = _run(tiny_root)
    assert result["correct"]
    assert all(c["value"] == 0 for c in result["checks"].values())


def test_state_left_unchanged_is_wrong(tiny_root, monkeypatch):
    monkeypatch.setattr(algebra.BfsAlgebra, "update",
                        lambda self, value, cand, depth, n: (value, jnp.zeros_like(value, bool)))
    result = _run(tiny_root)
    assert not result["correct"]
    assert result["failed"] == result["attempted"]
    assert result["checks"]["wrong_levels"]["value"] > 0


def test_altered_answer_is_wrong(tiny_root, monkeypatch):
    monkeypatch.setattr(algebra.BfsAlgebra, "finalize",
                        lambda self, value: jnp.where(value > 0, value ^ 1, value))
    result = _run(tiny_root)
    assert not result["correct"]
    assert result["checks"]["wrong_parents"]["value"] > 0
    assert result["checks"]["wrong_levels"]["value"] == 0


def test_control_is_wrong(tiny_root):
    for seed in (1, 2, 3):
        out = control.control(tiny_root, "g500-s22-1x1.bfs", seed, 4)
        assert not out["correct"]
        assert out["checks"]["wrong_levels"]["value"] > 0


_GRID = """
import json, sys, time
from pathlib import Path
sys.path[:0] = [{repo!r}, {src!r}]
import jax
from bench import cell
root = Path({root!r})
def run():
    return cell.run(root, "g500-s22-2x2.bfs", 5, 0.2, False, time.perf_counter(),
                    log=lambda m: None)
sound = run()
jax.lax.ppermute = lambda x, axis_name, perm: x  # the exchange left out
broken = run()
print(json.dumps([sound["correct"], broken["correct"],
                  broken["checks"]["wrong_levels"]["value"]]))
"""


def test_exchange_left_out_is_wrong(tiny_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _GRID.format(repo=str(REPO), src=str(REPO / "src"), root=str(tiny_root))
    proc = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    sound, broken, wrong = json.loads(proc.stdout.strip().splitlines()[-1])
    assert sound and not broken and wrong > 0
