"""Per-phase device time on synthetic traces: each op counts in its
innermost phase scope, as its compiled instruction's ``op_name`` gives it;
the new readers' values; silence on a program without the scopes; the
existing readers unchanged; and the compiled module chosen by matching the
trace's instructions."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import devtrace, phases, spec
from bench.cell import Reading

REPO = Path(__file__).resolve().parents[2]
JIT = "jit(_bfs_local)/while/body"
CELL = spec.Cell(name="t", chips=2, config={"grid": {"rows": 1, "cols": 2}},
                 traffic={}, end_to_end=[], per_layer=[])
PUSH = "bfs/expand/cond/branch_1_fun/bfs/expand/push"
#: (name, start, end, op_name in the compiled program), per chip.  Chip 0
#: runs the level loop holding a push pass (slab, residue, a row exchange
#: nested in it), the update with its termination psum, and an op the
#: compiler made; chip 1 runs the row-offset search and a column gather.
OPS = {
    0: [("while.1", 0.0, 9.0, "jit(_bfs_local)/while"),
        ("fusion.1", 0.0, 3.0, f"{JIT}/{PUSH}/slab/while/body/gather"),
        ("fusion.2", 3.0, 6.0, f"{JIT}/{PUSH}/residue/while/body/gather"),
        ("all-to-all.3", 6.0, 7.0, f"{JIT}/{PUSH}/bfs/row/pfor16[1024]/bfs/row/all_to_all"),
        ("fusion.4", 7.0, 7.5, f"{JIT}/{PUSH}/bfs/row/pfor16[1024]/bfs/row/and"),
        ("reduce-window.5", 7.5, 8.0, ""),
        ("all-reduce.6", 8.0, 9.0, f"{JIT}/bfs/update/bfs/termination/psum")],
    1: [("while.7", -1.0, 4.0, "jit(_bfs_local)/bfs/degree/bfs/row-offsets/while"),
        ("fusion.8", -1.0, 4.0, "jit(_bfs_local)/bfs/degree/bfs/row-offsets/while/body/gather"),
        ("all-gather.9", 4.0, 5.0, f"{JIT}/bfs/column/bfs/column/all_gather")],
}


def _text(name):
    opcode = name.split(".")[0]
    return f"%{name} = s32[8]{{0}} {opcode}(s32[8]{{0}} %p)"


def _view():
    """The trace of ``OPS`` over a 10 s window, as a TPU gives it: no
    metadata in the op texts."""
    ops = {dev: [devtrace.Op(n, t0, t1, n.split(".")[0], _text(n)) for n, t0, t1, _ in row]
           for dev, row in OPS.items()}
    return devtrace.TraceView(ops=ops, spans=[("window", 0.0, 10.0)], window=(0.0, 10.0))


def _module(scoped=True):
    """The compiled program of ``OPS``; ``scoped=False`` is the same program
    without the phase scopes."""
    return {n: ("s32[8]{0}", op_name if scoped else f"{JIT}/op")
            for row in OPS.values() for n, _, _, op_name in row}


@pytest.fixture
def compiled(monkeypatch):
    """Serve a given module as the compiled program in the compile cache."""
    def serve(module):
        monkeypatch.setattr(phases, "_from_compile_cache", lambda view, grid: module)
    return serve


def _read(name, view):
    return spec.load_reader(REPO, name)(Reading(view, None, CELL))


@pytest.mark.parametrize("op_name, want", [
    (f"{JIT}/bfs/expand/cond/branch_1_fun/bfs/expand/push/residue/while/body/gather",
     "bfs/expand/push/residue"),
    (f"{JIT}/bfs/expand/cond/branch_0_fun/bfs/expand/pull/bfs/unreached/"
     "bfs/unreached/all_gather", "bfs/unreached"),
    (f"{JIT}/bfs/expand/bfs/expand/pull/bfs/row-pull/bfs/row-pull/all_to_all",
     "bfs/row-pull"),
    (f"{JIT}/bfs/expand/and", "bfs/expand"),
    ("jit(_bfs_local)/bfs/degree/bfs/row-offsets/while/body/gather", "bfs/row-offsets"),
    ("jit(_bfs_local)/bfs/degree/jit(diff)/sub", "bfs/degree"),
    (f"{JIT}/bfs/row/bfs/row[btfly:0]/cond/branch_1_fun/bfs/row[btfly:0]/"
     "pfor16[256]/bfs/row[btfly:0]/ppermute", "bfs/row"),
    (f"{JIT}/bfs/update/bfs/termination/psum", "bfs/termination"),
    (f"{JIT}/sssp/values/sssp/transpose/ppermute", "sssp/transpose"),
    (f"{JIT}/bfs/rowing/add", None),
    ("jit(_bfs_local)/while", None),
    ("reduce_window_min", None),
    ("", None),
])
def test_innermost_phase(op_name, want):
    assert phases.phase(op_name) == want


def test_phase_families():
    assert phases.is_wire("bfs/row-pull") and phases.is_wire("bfs/termination")
    assert not phases.is_wire("bfs/expand/push") and not phases.is_wire(None)
    assert phases.is_expand("bfs/expand") and phases.is_expand("bfs/expand/pull/slab")
    assert phases.is_residue("bfs/expand/pull/residue")
    assert not phases.is_residue("bfs/expand/pull/slab") and not phases.is_residue(None)


def test_phase_readers(compiled):
    compiled(_module())
    v = _view()
    busy = devtrace.busy_s(v)  # chip 0: 0-9; chip 1: 0-5 (clipped)
    assert busy == pytest.approx(7.0)
    # row offsets: chip 1's search, 0-4 in the window
    assert _read("row_offsets_pct", v) == pytest.approx(100 * 4.0 / 2 / busy)
    # expansion: slab 0-3 and residue 3-6 on chip 0; the row exchange nested
    # in the pass is wire, not expansion
    assert _read("expand_pct", v) == pytest.approx(100 * 6.0 / 2 / busy)
    assert _read("expand_residue_pct", v) == pytest.approx(100 * 3.0 / 2 / busy)
    # wire: the row all-to-all and its packing (6-7.5), the termination psum
    # (8-9), and chip 1's column gather (4-5); the compiler's reduce-window
    # (7.5-8) lies in no phase
    assert _read("wire_pct", v) == pytest.approx(100 * (2.5 + 1.0) / 2 / busy)
    unnamed = phases.phase_s(v, lambda p: p is None, _module())
    assert unnamed == pytest.approx(0.5 / 2)


@pytest.mark.parametrize("module", [_module(scoped=False), {}], ids=["unscoped", "none"])
def test_phase_readers_are_silent_without_scopes(compiled, module):
    compiled(module)
    for name in ("row_offsets_pct", "expand_pct", "expand_residue_pct", "wire_pct"):
        assert _read(name, _view()) is None


def test_existing_readers_read_as_before(compiled):
    """The other device readers and the breakdown read the same view to the same
    numbers whether or not the phase readers ran on it first."""
    names = ("device_idle_pct", "kernels_pct", "collective_pct", "collective_exposed_pct")
    v = _view()
    before = [_read(n, v) for n in names], devtrace.top_ops(v), devtrace.idle_gaps(v)
    compiled(_module())
    assert _read("expand_pct", v) is not None
    after = [_read(n, v) for n in names], devtrace.top_ops(v), devtrace.idle_gaps(v)
    assert before == after
    assert before[0][2] == pytest.approx(100 * (1.0 + 1.0 + 1.0) / 2 / 10)


_HLO = """\
HloModule jit__bfs_local, entry_computation_layout={()}

ENTRY %main (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0), metadata={op_name="args[0]"}
  %fusion.1 = s32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="%s"}
  ROOT %fusion.2 = s32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%g
}
"""


def test_op_names_from_the_compiled_program():
    """Each traced op takes its ``op_name`` from the compiled module whose
    instructions (name and result type) the trace matches."""
    residue = f"{JIT}/bfs/expand/bfs/expand/push/residue/gather"
    module = phases.hlo_op_names(_HLO.replace("%s", residue))
    assert module["fusion.1"] == ("s32[8]{0}", residue)
    assert module["fusion.2"] == ("s32[8]{0}", "")
    other = {"fusion.1": ("pred[8]{0}", "x"), "fusion.2": ("pred[8]{0}", "")}
    bare = devtrace.TraceView(
        ops={0: [devtrace.Op(n, t0, t1, "fusion", _text(n))
                 for n, t0, t1 in (("fusion.1", 0.0, 1.0), ("fusion.2", 1.0, 2.0))]},
        spans=[], window=(0.0, 2.0))
    assert phases.best_module(bare, [other, module]) is module
    assert phases.best_module(bare, [other]) == {}
    op1, op2 = bare.ops[0]
    assert phases.op_phase(op1, module) == "bfs/expand/push/residue"
    assert phases.op_phase(op2, module) is None
    assert phases.op_phase(op1, other) is None  # same name, another result type
    assert phases.phase_s(bare, phases.is_residue, module) == pytest.approx(1.0)



_CACHED = r"""
import os, sys
os.environ["JAX_COMPILATION_CACHE_DIR"] = sys.argv[1]
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from bench import devtrace, phases
from repro.core import csr, distributed_bfs as dbfs
from repro.graphgen import builder, kronecker
from repro.launch import runtime

g = builder.build_csr(kronecker.kronecker_edges(10, seed=1), n=1 << 10)
mesh = runtime.grid_mesh(1, 1)
bg = csr.partition_2d(g, rows=1, cols=1)
cfg = dbfs.DistBFSConfig(policy="direction_opt", expand="hybrid")
fn = dbfs.build_bfs(mesh, bg, cfg)
blocks = dbfs.shard_blocked(mesh, bg, cfg)
jax.block_until_ready(fn(*blocks, 3))
text = fn.lower_call(*blocks, 3).compile().as_text()
module = phases.hlo_op_names(text)
ops = [devtrace.Op(n, 0.0, 1.0, "", f"%{n} = {t} op()") for n, (t, _) in module.items()]
view = devtrace.TraceView(ops={0: ops}, spans=[], window=(0.0, 1.0))
read = phases.module_of(view, (1, 1))
named = {phases.phase(o) for _, o in read.values()}
print("READ", len(read) == len(module), "bfs/row-offsets" in named,
      "bfs/expand/push/residue" in named)
"""


def test_compiled_program_reads_back_from_the_compile_cache(tmp_path):
    """The program a run compiled is read back from JAX's persistent compile
    cache with each instruction's ``op_name`` (here on the CPU)."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]))
    out = subprocess.run([sys.executable, "-c", _CACHED, str(tmp_path / "jax")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "READ True True True" in out.stdout


def test_unreadable_cache_entry_is_logged_and_skipped(tmp_path, monkeypatch, capsys):
    """A compile-cache entry that cannot be read back is named on stderr,
    and the reader then finds no program (the phase metrics read None)."""
    (tmp_path / f"{phases.PROGRAM}-junk-cache").write_bytes(b"not an executable")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    view = devtrace.TraceView(
        ops={0: [devtrace.Op("fusion.1", 0.0, 1.0, "fusion", _text("fusion.1"))]},
        spans=[], window=(0.0, 1.0))
    assert phases.module_of(view, (1, 1)) == {}
    assert f"skipped {phases.PROGRAM}-junk-cache" in capsys.readouterr().err
