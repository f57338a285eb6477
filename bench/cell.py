"""One run of one cell: set-up, the measured window, the comparison.

Set-up makes the configuration's graph (a Kronecker edge list from the
configuration's own graph seed) and draws the roots from ``--seed``
(``bench.kronecker``).  The graph is the deployment's fixed dataset: the
program's compiled shapes follow from it (edge capacity per block, hybrid
slab width and residue), so one graph per configuration lets every run
after the first load its program from the compile cache.  Set-up then hands the edge list to the program: kernel 1 (``build_csr``,
``partition_2d``), placement (``build_bfs``, ``shard_blocked``) on a grid
mesh of the cell's chips, and one warm-up search that compiles, or loads
from the compile cache, the one program the window calls.

The window is Graph500's timed loop: one client, a closed loop, one call
per batch of roots, each waited for with ``block_until_ready``, back to
back, cycling through the traffic's roots.  It runs from the first
dispatch until the first search that completes after ``seconds``; that
search counts.  The answers stay on the device until the window has
closed; then the peak device memory is read, the answers are copied to
the host, the program's state is freed, and every answer is held to the
reference (``bench.reference``).
"""

from __future__ import annotations

import dataclasses
import glob
import itertools
import shutil
import time
from pathlib import Path

import numpy as np

from bench import devtrace, kronecker, reference, spec


@dataclasses.dataclass
class Window:
    t0: float  # first dispatch
    t1: float  # completion of the last search
    batches: list  # the roots of each call
    outs: list  # each call's answer, still on the device

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def drive(call, block, batches, seconds: float, spans, clock=time.perf_counter) -> Window:
    """Run ``call(batch)`` back to back until a call completes at or after
    ``seconds`` from the first dispatch; that call counts."""
    done, outs = [], []
    t0 = t = clock()
    for batch in batches:
        with spans("dispatch"):
            out = call(batch)
        with spans("wait"):
            block(out)
        t = clock()
        done.append(batch)
        outs.append(out)
        if t - t0 >= seconds:
            break
    return Window(t0=t0, t1=t, batches=done, outs=outs)


def teps(traversed, window_s: float) -> float:
    """Traversed input edges of all searches in the window over its wall
    time, in millions per second."""
    return sum(traversed) / window_s / 1e6


def schedule(roots: np.ndarray, batch: int):
    """Calls of ``batch`` roots each, cycling through ``roots``; a batch
    of one is the program's scalar-root call."""
    for k in itertools.count():
        lo = (k * batch) % len(roots)
        picked = np.take(roots, range(lo, lo + batch), mode="wrap")
        yield int(picked[0]) if batch == 1 else picked


class ProgramSearch:
    """The system under test: kernel 1 and placement on ``mesh``, and
    the compiled search."""

    def __init__(self, mesh, edges: np.ndarray, n: int, config: dict, spans):
        from repro.core import csr, distributed_bfs as dbfs
        from repro.graphgen import builder

        import jax

        grid = config["grid"]
        with spans("kernel1.csr"):
            g = builder.build_csr(edges, n=n)
        with spans("kernel1.partition"):
            bg = csr.partition_2d(g, rows=grid["rows"], cols=grid["cols"])
        del g
        cfg = dbfs.DistBFSConfig(**config["path"])
        with spans("kernel1.placement"):
            self.fn = dbfs.build_bfs(mesh, bg, cfg)
            self.blocks = dbfs.shard_blocked(mesh, bg, cfg)
            jax.block_until_ready(self.blocks)
        self.n = n

    def __call__(self, roots):
        return self.fn(*self.blocks, roots)

    def fetch(self, out) -> list:
        """Host ``(parent, level)`` of each root of one call."""
        parent, level, _ = out
        parent = np.asarray(parent).reshape(-1, np.shape(parent)[-1])[:, : self.n]
        level = np.asarray(level).reshape(-1, np.shape(level)[-1])[:, : self.n]
        return list(zip(parent, level))

    def release(self) -> None:
        for a in self.blocks:
            a.delete()
        self.blocks = self.fn = None


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def _warm_root(deg: np.ndarray, roots: np.ndarray, batch: int):
    """Roots for the warm-up call: isolated vertices where the graph has
    them, so the call compiles the window's program and ends after one
    level."""
    pick = np.flatnonzero(deg == 0)[:batch]
    if pick.size < batch:
        pick = roots[:batch]
    return int(pick[0]) if batch == 1 else pick.astype(np.int32)


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, log=print) -> dict:
    """Run the cell once; returns the result line as a dict."""
    import jax
    from repro.launch import runtime

    cell = spec.load_cell(root, workload)
    spans = devtrace.Spans()
    graph, grid, traffic = cell.config["graph"], cell.config["grid"], cell.traffic
    n = 1 << graph["scale"]
    devices = jax.devices()
    mesh = runtime.grid_mesh(grid["rows"], grid["cols"])

    with spans("generate"):
        edges = kronecker.kronecker_edges(graph["seed"], graph["scale"], graph["edgefactor"],
                                          graph["A"], graph["B"], graph["C"])
        deg = kronecker.degrees(edges, n)
        roots = kronecker.valid_roots(deg, traffic["roots"], seed)
    search = ProgramSearch(mesh, edges, n, cell.config, spans)
    with spans("warmup"):
        jax.block_until_ready(search(_warm_root(deg, roots, traffic["batch"])))
    log("set-up: " + ", ".join(f"{k} {t1 - t0:.3f} s" for k, t0, t1 in spans.items))

    trace_dir = root / ".bench_out" / "trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    with spans("window"):
        window = drive(search, jax.block_until_ready,
                       schedule(roots, traffic["batch"]), seconds, spans)
    if trace:
        jax.profiler.stop_trace()
    memory_peak = _peak_bytes(mesh.devices.flat)
    with spans("fetch"):
        answers = [a for out in window.outs for a in search.fetch(out)]
    window.outs.clear()
    search.release()
    del search
    searched = [int(r) for b in window.batches for r in np.atleast_1d(b)]
    log(f"window: {len(window.batches)} calls, {len(searched)} searches in "
        f"{window.seconds:.3f} s")

    with spans("reference"):
        ref = reference.Reference(edges, n)
        verdict = reference.compare(ref, searched, answers)
    log(f"reference: {spans.total('reference'):.3f} s")

    result = {
        "correct": verdict.correct,
        "attempted": verdict.searches,
        "failed": verdict.wrong_searches,
        "metrics": {},
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                   "count": len(devices), "memory_peak_bytes": memory_peak},
    }
    if not trace:
        values = {"teps": teps(verdict.traversed, window.seconds),
                  "setup_s": window.t0 - t_start}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        view = devtrace.load(_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["device"]["busy_s"] = devtrace.busy_s(view)
        result["device"]["window_s"] = view.window_s
        reading = Reading(trace=view, spans=spans, cell=cell)
        for m in cell.per_layer:
            value = spec.load_reader(root, m["name"])(reading)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": devtrace.top_ops(view),
                               "idle_gaps": devtrace.idle_gaps(view)}
    result["checks"] = verdict.numbers()
    return result


@dataclasses.dataclass
class Reading:
    """What a per-layer reader gets: the reduced trace of the window, the
    benchmark's host spans, and the cell."""

    trace: devtrace.TraceView
    spans: devtrace.Spans
    cell: spec.Cell


def _xplane(trace_dir: Path) -> str:
    found = sorted(glob.glob(str(trace_dir / "plugins" / "profile" / "*" / "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    return found[-1]
