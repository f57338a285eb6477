"""The BFS program's phases on the device trace, read from op metadata.

The program runs each phase of its level loop under a ``jax.named_scope``
named like the phase's CommStats zone, so a device op's ``op_name``
metadata reads like ``jit(_bfs_local)/while/body/bfs/expand/cond/
branch_1_fun/bfs/expand/push/residue/while/body/gather``.  An op belongs to
its innermost phase: the last phase scope in that name, with the
expansion's direction (``push``, ``pull``) and part (``slab``,
``residue``) when they follow it.  A butterfly stage (``bfs/row[btfly:0]``)
belongs to its exchange (``bfs/row``); a wire format scope
(``bfs/row/pfor16[1024]``) names the bucket an exchange ran and is not a
phase of its own.  A program without these scopes, and an op the compiler
made itself (it carries no metadata), have no phase.

Where ``op_name`` comes from: a TPU trace names each op event by its HLO
instruction text without the metadata, and its events carry no op-name
statistic.  So the compiled program the window ran is read back from JAX's
persistent compile cache, which the benchmark keeps in its checkout, and
each traced op takes the ``op_name`` of the instruction with its name and
result type.  JAX keys that cache with debug information stripped, so a
scope is not part of the key: after a change to scopes alone, a warm
cache serves the older program's metadata, and only a cold cache reads
the new names.  Each cache entry that cannot be read is logged.
"""

from __future__ import annotations

import glob
import os
import re
import sys

from bench import devtrace

#: phases of the wire plans: collectives and their compaction and packing
WIRE = ("transpose", "column", "row", "row-pull", "unreached", "termination",
        "degree", "values")
_PHASE = re.compile(
    r"(?:^|/)(bfs|sssp|cc|pagerank)/"
    r"(row-offsets|row-pull|row|column|transpose|unreached|termination|degree|"
    r"values|update|expand(?:/(?:push|pull)(?:/(?:slab|residue))?)?)"
    r"(?:\[[^\]/]*\])?(?=/|$)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\S+)")
PROGRAM = "jit__bfs_local"  # the module name of the BFS program


def phase(op_name: str) -> str | None:
    """The innermost phase of an op, such as ``bfs/expand/push/residue``,
    or None outside every phase."""
    last = None
    for last in _PHASE.finditer(op_name):
        pass
    return f"{last.group(1)}/{last.group(2)}" if last else None


def is_wire(name: str | None) -> bool:
    return name is not None and name.split("/", 1)[1] in WIRE


def is_expand(name: str | None) -> bool:
    return name is not None and name.split("/", 1)[1].startswith("expand")


def is_residue(name: str | None) -> bool:
    return is_expand(name) and name.endswith("/residue")


# -- each traced op's op_name, from the compiled program ---------------------

_MODULE: dict = {}  # id(view) -> (view, module)


def op_phase(op: devtrace.Op, module: dict) -> str | None:
    """The innermost phase of a traced op, from the compiled ``module``'s
    instruction of the same name and result type."""
    entry = module.get(op.name)
    return phase(entry[1]) if entry and entry[0] == _result_type(op.text) else None


def module_of(view: devtrace.TraceView, grid: tuple[int, int]) -> dict:
    """The compiled BFS program as ``hlo_op_names`` gives it, read from the
    compile cache; empty when no cached program matches the trace.
    ``grid`` names the devices the program was compiled for."""
    got = _MODULE.get(id(view))
    if got is not None and got[0] is view:
        return got[1]
    module = _from_compile_cache(view, grid) if view.ops else {}
    _MODULE.clear()
    _MODULE[id(view)] = (view, module)
    return module


def hlo_op_names(text: str) -> dict[str, tuple[str, str]]:
    """``{instruction name: (result type, op_name)}`` of a compiled module's
    HLO text."""
    out = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = (m.group(2), op.group(1) if op else "")
    return out


def best_module(view: devtrace.TraceView, modules) -> dict:
    """Of the ``hlo_op_names`` of several compiled modules, the one whose
    instructions (name and result type) the trace's ops match most often;
    empty when none matches half of them."""
    seen = {(o.name, _result_type(o.text)) for ops in view.ops.values() for o in ops}
    best, hits = {}, 0
    for module in modules:
        n = sum(1 for name, head in seen if module.get(name, ("",))[0] == head)
        if n > hits:
            best, hits = module, n
    return best if hits * 2 > len(seen) else {}


def _result_type(text: str) -> str:
    return text.split(" = ", 1)[-1].split(" ", 1)[0]


def _from_compile_cache(view, grid) -> dict:
    root = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not root:
        return {}
    modules = []
    for path in sorted(glob.glob(os.path.join(root, PROGRAM + "-*-cache"))):
        try:
            modules.append(hlo_op_names(_cached_module_text(path, grid)))
        except Exception as e:  # noqa: BLE001 - e.g. compiled for other devices
            print(f"bench: phases: skipped {os.path.basename(path)}: "
                  f"{type(e).__name__}: {str(e)[:200]}", file=sys.stderr)
    return best_module(view, modules)


def _cached_module_text(path: str, grid) -> str:
    from jax._src import compilation_cache
    from jax._src.lib import xla_client

    from repro.launch import runtime

    with open(path, "rb") as f:
        entry = compilation_cache.decompress_executable(f.read())
    serialized, _ = compilation_cache.extract_executable_and_time(entry)
    devices = xla_client.DeviceList(tuple(runtime.grid_mesh(*grid).devices.flat))
    executable = devices[0].client.deserialize_executable(serialized, devices, None)
    return executable.hlo_modules()[0].to_string()


# -- device time by phase ------------------------------------------------------


def phase_s(view: devtrace.TraceView, keep, module: dict) -> float:
    """Device seconds of the leaf ops whose innermost phase ``keep``
    accepts, clipped to the window, as a mean over the chips."""

    def one(v, dev):
        spans = [(o.start, o.end) for o in devtrace.leaves(v.ops[dev])
                 if keep(op_phase(o, module))]
        return devtrace.length(devtrace.clip(devtrace.union(spans), *v.window))

    return devtrace.mean_over_devices(view, one)


def share(reading, keep) -> float | None:
    """``phase_s`` over device busy time, in percent; None when no op of
    the window lies in such a phase (a program without the scopes)."""
    view = reading.trace
    grid = reading.cell.config["grid"]
    s = phase_s(view, keep, module_of(view, (grid["rows"], grid["cols"])))
    return 100.0 * s / devtrace.busy_s(view) if s > 0 else None
