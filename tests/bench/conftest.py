"""Fixtures of the benchmark's CPU tests: a copy of the benchmark's
files with its graphs cut to a size a test can hold."""

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def make_root(dest: Path, scale: int = 12, roots: int = 8) -> Path:
    """``dest`` with BENCHMARK.json, the traffic, the readers, and every
    configuration at ``scale``.  At scale 12 each chip of a 2x2 grid owns
    a 1024-vertex chunk (smaller graphs leave three chips only padding)."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (dest / "bench" / "configs").mkdir(parents=True)
    shutil.copytree(REPO / "bench" / "metrics", dest / "bench" / "metrics")
    shutil.copytree(REPO / "bench" / "traffic", dest / "bench" / "traffic")
    for c in bench["configs"]:
        config = json.loads((REPO / c["file"]).read_text())
        config["graph"]["scale"] = scale
        (dest / c["file"]).write_text(json.dumps(config))
    for path in (dest / "bench" / "traffic").glob("*.json"):
        traffic = json.loads(path.read_text())
        traffic["roots"] = roots
        path.write_text(json.dumps(traffic))
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


@pytest.fixture
def repo():
    return REPO


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
