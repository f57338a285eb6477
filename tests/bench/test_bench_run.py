"""The harness end to end on the CPU at a small size: the result line's
schema, and no result at all without a TPU."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import cell, run

REPO = Path(__file__).resolve().parents[2]


def _number(x):
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def _check_schema(result, metric_names, trace):
    assert list(result)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert isinstance(result["correct"], bool)
    assert result["attempted"] > 0 and 0 <= result["failed"] <= result["attempted"]
    for name, m in result["metrics"].items():
        assert name in metric_names and _number(m["value"]) and isinstance(m["unit"], str)
    dev = result["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert _number(dev["busy_s"]) and dev["window_s"] > 0
        for key in ("device_ops", "idle_gaps"):
            assert len(result["breakdown"][key]) <= 10
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("trace", [False, True])
def test_result_line_schema(tiny_root, trace, capsys):
    result = cell.run(tiny_root, "g500-s22-1x1.bfs", 2**33 + 1, 0.3, trace,
                      time.perf_counter(), log=lambda m: None)
    wanted = {"teps", "setup_s"} if not trace else {
        "device_idle_pct", "kernels_pct", "kernel1_s"}
    _check_schema(result, wanted, trace)
    assert result["correct"] and result["failed"] == 0
    if not trace:
        assert set(result["metrics"]) == {"teps", "setup_s"}
        assert result["metrics"]["teps"]["unit"] == "Medges/s"
    else:
        assert "kernel1_s" in result["metrics"]  # the CPU trace has no device plane
    run.report(result)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == json.loads(json.dumps(result))
    assert err.strip().splitlines()[-1] == "correct: True"
    assert err.strip().splitlines()[-2].startswith("check missing_searches: 0 (limit 0)")


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500-s22-1x1.bfs",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr
