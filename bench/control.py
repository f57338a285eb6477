"""The control of the comparison that decides ``correct``: the reference
put in the program's place, breaking the configuration's guarantee of
exact levels by stopping each search one level early (the deepest level's
vertices come back unreached).  The comparison has to call it wrong.

    python3 bench/control.py --workload g500-s22-1x1.bfs --searches 4 --seeds 11 12 13

For each seed it makes the cell's graph and roots as a run does, answers
the first ``--searches`` calls of the schedule with the control, and
prints one JSON line of the compared numbers.  The benchmark's own runs
never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def early_stop(ref, root: int):
    """The reference's answer for ``root``, one level short."""
    level = ref.levels([root])[0]
    level[level == level.max()] = -1  # roots have an edge: the deepest level is > 0
    return ref.parents(root, level), level


def control(root: Path, workload: str, seed: int, searches: int) -> dict:
    from bench import cell as cellmod
    from bench import kronecker, reference, spec

    cell = spec.load_cell(root, workload)
    graph, traffic = cell.config["graph"], cell.traffic
    n = 1 << graph["scale"]
    edges = kronecker.kronecker_edges(graph["seed"], graph["scale"], graph["edgefactor"],
                                          graph["A"], graph["B"], graph["C"])
    roots = kronecker.valid_roots(kronecker.degrees(edges, n), traffic["roots"], seed)
    calls = cellmod.schedule(roots, traffic["batch"])
    searched = [int(r) for _, b in zip(range(searches), calls) for r in np.atleast_1d(b)]
    ref = reference.Reference(edges, n)
    answers = [early_stop(ref, r) for r in searched]
    verdict = reference.compare(ref, searched, answers)
    return {"seed": seed, "correct": verdict.correct, "checks": verdict.numbers()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--searches", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    device = jax.devices()[0]
    for seed in args.seeds:
        out = control(ROOT, args.workload, seed, args.searches)
        out["device"] = {"platform": device.platform, "kind": device.device_kind}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    if str(Path(__file__).resolve().parent) == str(Path(sys.path[0]).resolve()):
        sys.path.pop(0)
    sys.exit(main())
