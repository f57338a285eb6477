"""BENCHMARK.json against the rules its format keeps, and discovery of
configurations, traffic mixes and per-layer readers by name."""

import json
import re
from pathlib import Path

from bench import spec

REPO = Path(__file__).resolve().parents[2]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_benchmark_json_keys_and_names():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    for p in b["paths"]:
        assert (REPO / p).is_dir() and re.match(r"^[A-Za-z0-9_./-]{1,200}$", p)
    assert b["command"][1] in {f"{b['paths'][0]}/run.py"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in b[k]]
    assert len(names) == len(set(names)) and all(NAME.match(x) for x in names)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (REPO / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
    assert {m["name"] for m in b["end_to_end"]} >= {"setup_s"}
    for m in b["end_to_end"]:
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)


def test_every_cell_finds_its_files_and_readers():
    b = _bench()
    e2e = {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        cell = spec.load_cell(REPO, w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e and m["source"] in SOURCES
            assert callable(spec.load_reader(REPO, m["name"]))


def test_new_files_are_found_by_name(tiny_root):
    """A configuration, a traffic mix and a reader added as files only,
    and named in BENCHMARK.json, are picked up with no other edit."""
    cfg = json.loads((tiny_root / "bench/configs/g500-s22-1x1.json").read_text())
    cfg["graph"]["scale"] = 9
    (tiny_root / "bench/configs/other.json").write_text(json.dumps(cfg))
    (tiny_root / "bench/traffic/mix-b2.json").write_text(
        json.dumps({"roots": 6, "batch": 2}))
    (tiny_root / "bench/metrics/answer_pct.py").write_text(
        "def read(reading):\n    return 42.0\n")
    b = json.loads((tiny_root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "other", "source": "x", "file": "bench/configs/other.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "other.mix-b2", "config": "other", "traffic": "mix-b2",
                           "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "answer_pct", "unit": "%", "better": "higher",
                           "source": "host_clock", "layer": "device", "moves": "teps"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = spec.load_cell(tiny_root, "other.mix-b2")
    assert cell.config["graph"]["scale"] == 9
    assert cell.traffic == {"roots": 6, "batch": 2}
    # a metric without a workloads key is read in every cell reporting teps
    assert "answer_pct" in [m["name"] for m in cell.per_layer]
    assert "answer_pct" in [m["name"] for m in
                            spec.load_cell(tiny_root, "g500-s22-1x1.bfs").per_layer]
    assert spec.load_reader(tiny_root, "answer_pct")(None) == 42.0


def test_cell_reports_only_its_listed_metrics():
    one = [m["name"] for m in spec.load_cell(REPO, "g500-s22-1x1.bfs").per_layer]
    four = [m["name"] for m in spec.load_cell(REPO, "g500-s22-2x2.bfs").per_layer]
    assert "collective_pct" not in one and "collective_pct" in four
