"""Device scopes of the distributed BFS: each phase of the level loop is
named in the compiled program, as the benchmark's trace readers read it.

The program runs each phase under a ``jax.named_scope`` (op metadata, no op
of its own) and :func:`bench.phases.phase` reads an op's innermost phase
from its ``op_name``.  One subprocess with four host devices compiles the
program at scale 14 for every (expansion, policy) pair on a 2x2 grid, and
hybrid x direction_opt on one device, and writes the compiled HLO text of
each; the cases below read it.  At scales 10 and 12 the 2x2 grid's
row exchange has no sparse bucket and so no bucket switch to check;
scale 14 has one.

Ops the compiler makes itself (copies, the reduce-window trees of a running
minimum, hoisted broadcasts) carry no program metadata, so no scope can name
them; they are told apart here by that metadata, and their device time is
measured on the chip (PERF.md: time outside every phase).
"""

import json
import os
import re
import subprocess
import sys

import pytest

from bench import phases

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CASES = [("2x2", e, p) for e in ("coo", "ell", "hybrid")
         for p in ("top_down", "bottom_up", "direction_opt")]
CASES.append(("1x1", "hybrid", "direction_opt"))

_SCRIPT = r"""
import json, sys
import jax, jax.numpy as jnp
from repro.core import csr as csrmod, distributed_bfs as dbfs
from repro.graphgen import builder, kronecker
from repro.launch import runtime

out, cases = sys.argv[1], json.loads(sys.argv[2])
g = builder.build_csr(kronecker.kronecker_edges(14, seed=3), n=1 << 14)
for grid, expand, policy in cases:
    rows, cols = map(int, grid.split("x"))
    mesh = runtime.grid_mesh(rows, cols)
    bg = csrmod.partition_2d(g, rows=rows, cols=cols)
    cfg = dbfs.DistBFSConfig(mode="auto", policy=policy, expand=expand)
    fn = dbfs.build_bfs(mesh, bg, cfg)
    blocks = dbfs.shard_blocked(mesh, bg, cfg)
    text = fn.lower_call(*blocks, jnp.int32(0)).compile().as_text()
    with open(f"{out}/{grid}-{expand}-{policy}.hlo", "w") as f:
        f.write(text)
"""


@pytest.fixture(scope="module")
def compiled(tmp_path_factory):
    out = tmp_path_factory.mktemp("scopes")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    run = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(out), json.dumps(CASES)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert run.returncode == 0, run.stderr[-3000:]
    return out


# -- compiled HLO text ---------------------------------------------------------

_COMP = re.compile(r"^(ENTRY )?%(\S+) .*\{\s*$")
_INST = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*?\s([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:body|condition|true_computation|false_computation)=%([\w.-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
#: the wire formats a dispatch branch is named after (``repro.comm.formats``)
_FORMAT = re.compile(
    r"(?:^|/)[a-z]+/[a-z-]+(?:\[[^\]/]*\])?/"
    r"(pfor\d+\[\d+\]|bitmap(?:\+p\d+)?|dense-i32|raw-id)(?=/)")
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|all-to-all|collective-permute|"
                        r"reduce-scatter)(-start)?$")


def _parse(text):
    """{computation: [(name, opcode, op_name, [computations it runs])]},
    and the entry computation's name.  Fused and reducer computations are
    not followed: a fusion's own metadata names it."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None and (i := _INST.match(line)):
            op_name = _OP_NAME.search(line)
            runs = _CALLS.findall(line)
            if b := _BRANCHES.search(line):
                runs += [c.strip().lstrip("%") for c in b.group(1).split(",")]
            comps[cur].append((i.group(1), i.group(2),
                               op_name.group(1) if op_name else "", runs))
    return comps, entry


def _reach(comps, roots, into_conditionals=True):
    """Every instruction of the computations ``roots`` run, transitively."""
    seen, stack, out = set(), list(roots), []
    while stack:
        c = stack.pop()
        if c in seen:
            continue
        seen.add(c)
        for inst in comps.get(c, []):
            out.append(inst)
            if into_conditionals or inst[1] != "conditional":
                stack += inst[3]
    return out


def _program_made(op_name: str) -> bool:
    """Metadata the program wrote: a JAX primitive under the jit's name
    stack.  The compiler's own ops have none, a bare jit prefix, or an
    instruction name (``.../broadcast.123``) in its place."""
    last = op_name.rsplit("/", 1)[-1]
    return (op_name.startswith("jit(") and not last.startswith("jit(")
            and not re.search(r"\.\d+$", last))


@pytest.fixture
def program(compiled, request):
    """(policy, expansion backend, computations, entry, the level loop's
    ops)."""
    grid, expand, policy = request.param
    comps, entry = _parse((compiled / f"{grid}-{expand}-{policy}.hlo").read_text())
    loops = [i for i in comps[entry] if i[1] == "while" and phases.phase(i[2]) is None]
    assert len(loops) == 1, [i[:3] for i in loops]  # the level loop
    return policy, expand, comps, entry, _reach(comps, loops[0][3])


@pytest.mark.parametrize("program", CASES, indirect=True, ids="-".join)
def test_level_loop_ops_lie_in_one_phase(program):
    """Every fusion, collective and loop of the level loop that carries
    the program's metadata lies under a phase scope, and every collective
    of the program is named by its CommStats phase."""
    _, _, comps, entry, loop = program
    kinds = [i for i in loop if i[1] in ("fusion", "while", "conditional")
             or COLLECTIVE.match(i[1])]
    unnamed = [i[:3] for i in kinds if _program_made(i[2]) and phases.phase(i[2]) is None]
    assert not unnamed, unnamed
    named = {phases.phase(i[2]) for i in kinds}
    assert {"bfs/transpose", "bfs/column", "bfs/update", "bfs/termination"} <= named
    assert any(phases.is_expand(n) for n in named), named
    everything = _reach(comps, [entry])
    collectives = [i[:3] for i in everything if COLLECTIVE.match(i[1])]
    assert collectives and all(phases.is_wire(phases.phase(n)) for _, _, n in collectives), \
        collectives


@pytest.mark.parametrize("program", CASES, indirect=True, ids="-".join)
def test_expansion_is_slab_or_residue(program):
    """Expansion work runs under ``slab`` (ELL) or ``residue`` (COO) of a
    push or pull pass, as the backend stores the block, and the row-offset
    searches run outside the level loop."""
    _, expand, comps, entry, loop = program
    parts = {phases.phase(i[2]) for i in loop if i[1] == "fusion"}
    parts = {p.rsplit("/", 1)[-1] for p in parts if phases.is_expand(p)}
    parts -= {"expand", "push", "pull"}  # masks and the two parts' merge
    want = {"coo": {"residue"}, "ell": {"slab"}, "hybrid": {"slab", "residue"}}
    assert parts == want[expand], parts
    offsets = [i for i in _reach(comps, [entry]) if phases.phase(i[2]) == "bfs/row-offsets"]
    assert all(i not in loop for i in offsets)


@pytest.mark.parametrize("program", CASES, indirect=True, ids="-".join)
def test_dispatch_branches_name_their_format(program):
    """Each branch of an adaptive exchange's bucket switch runs under
    ``{phase}/{format}``: one format per branch, a different one each."""
    policy, _, comps, entry, loop = program
    switches = 0
    for name, opcode, op_name, runs in loop:
        if opcode != "conditional":
            continue
        formats = [{m.group(1) for _, _, n, _ in _reach(comps, [c], False)
                    for m in _FORMAT.finditer(n)} for c in runs]
        if not any(formats):
            continue  # a direction pass, not a bucket switch
        switches += 1
        assert all(len(f) == 1 for f in formats), (name, formats)
        assert len({f.pop() for f in formats}) == len(runs), name
    # at these chunk widths only the push row exchange has sparse buckets
    assert switches >= (policy != "bottom_up")
