"""End-to-end Graph500 harness (paper Algorithm 1) on the distributed driver.

Generation (untimed) -> Kernel 1: CSR construction + 2D partition (timed)
-> Kernel 2: 64 BFS searches from the spec's valid-root sample, traversed
in batches of B sources through the distributed 2D driver on a grid of
``jax.devices()`` (chips on a TPU host, forced host devices on the CPU;
every column/row collective executes for real) -> per-tree
Graph500 validation -> harmonic-mean TEPS via :mod:`benchmarks.teps`.
The codec comparison of earlier revisions lives on in the frontier-bytes
report: per-level frontier ids are priced raw vs compressed.

    PYTHONPATH=src python examples/graph500_benchmark.py --grid 2x2 --scale 13

64 roots is the spec's count; ``--roots 8`` keeps CPU smoke runs short.
With ``--batch B`` each timed kernel traverses B sources at once, so the
per-source time is dt/B — the TEPS statistic stays per-search, as the
spec defines it.
"""

import argparse
import os
import sys

# the TEPS helpers live in the top-level benchmarks package
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ap = argparse.ArgumentParser()
ap.add_argument("--grid", default="2x2")
ap.add_argument("--scale", type=int, default=13)
ap.add_argument("--edgefactor", type=int, default=16)
ap.add_argument("--roots", type=int, default=64, help="spec says 64")
ap.add_argument("--batch", type=int, default=8,
                help="sources traversed per timed kernel (B planes)")
ap.add_argument("--mode", default="auto",
                choices=["raw", "bitmap", "auto", "btfly"])
ap.add_argument("--policy", default="direction_opt",
                choices=["top_down", "bottom_up", "direction_opt"])
ap.add_argument("--expand", default="hybrid",
                choices=["coo", "ell", "hybrid", "auto"])
ap.add_argument("--codec", default="bp128d")
ap.add_argument("--no-validate", action="store_true",
                help="skip the per-tree Graph500 5-rule validation")
args = ap.parse_args()
ROWS, COLS = (int(x) for x in args.grid.split("x"))
# only the CPU platform's device count: on a TPU the grid is built from
# the chips themselves
os.environ.setdefault(
    "XLA_FLAGS", f"--xla_force_host_platform_device_count={ROWS * COLS}"
)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import teps  # noqa: E402
from repro.comm import registry  # noqa: E402
from repro.core import distributed_bfs as dbfs  # noqa: E402
from repro.core import validate  # noqa: E402
from repro.launch import graph500, runtime  # noqa: E402


def main() -> None:
    runtime.enable_compile_cache()
    print(f"# Graph500 scale={args.scale} edgefactor={args.edgefactor} "
          f"grid={ROWS}x{COLS} batch={args.batch} mode={args.mode} "
          f"policy={args.policy} expand={args.expand}")
    mesh = runtime.grid_mesh(ROWS, COLS)
    graph = graph500.build_graph(args.scale, args.edgefactor, ROWS, COLS)
    g, bg = graph.g, graph.bg
    print(f"Kernel1 (construction + 2D partition): "
          f"{graph.csr_s + graph.partition_s:.3f}s  m={g.m:,}  "
          f"chunk s={bg.part.chunk:,}  e_cap={bg.e_cap:,}")

    if args.roots % args.batch:
        raise SystemExit(f"--roots {args.roots} must be a multiple of "
                         f"--batch {args.batch}")
    roots = teps.valid_roots(g, args.roots, seed=2)

    cfg = dbfs.DistBFSConfig(mode=args.mode, policy=args.policy,
                             expand=args.expand)
    search = graph500.Search(mesh, bg, cfg)
    codec = registry.make_codec(args.codec)  # factory call OUTSIDE Kernel 2

    # warm-up compile (untimed, like the spec's untimed setup)
    search(roots[: args.batch])

    teps_list, comm_raw, comm_comp = [], 0, 0
    for lo in range(0, args.roots, args.batch):
        chunk = roots[lo : lo + args.batch]
        parent_np, level_np, depth, dt = search(chunk)
        per_source = dt / args.batch
        if not args.no_validate:
            graph500.check_trees(g, chunk, parent_np, level_np)
        for k in range(len(chunk)):
            te = validate.traversed_edges(g, parent_np[k])
            teps_list.append(te / per_source)
            lv = level_np[k]
            for d in range(1, depth + 1):
                ids = np.nonzero(lv == d)[0].astype(np.uint32)
                if ids.size:
                    comm_raw += ids.size * 4
                    comm_comp += len(codec.encode(ids))
        print(f"  roots[{lo}:{lo + args.batch}]: {dt:.3f}s "
              f"({per_source:.3f}s/source)  depth={depth}  "
              f"min TEPS {min(teps_list[lo:]):.3e}")

    hm = teps.harmonic_mean(teps_list)
    print(f"\nTEPS harmonic mean over {args.roots} roots "
          f"(batch {args.batch}, {len(jax.devices())}x "
          f"{jax.devices()[0].device_kind}): {hm:.3e}")
    print(f"frontier bytes: raw={comm_raw:,} {args.codec}={comm_comp:,} "
          f"({100 * (1 - comm_comp / max(comm_raw, 1)):.1f}% reduction — "
          f"paper: >90%)")


if __name__ == "__main__":
    main()
