"""Distributed 2D-partitioned BFS with compressed collectives (paper Alg. 4).

Runs on a grid of ``jax.devices()`` (chips on a TPU host, forced host
devices on the CPU) so the full column/row collective pipeline
(TransposeVector ppermute -> compressed all-gather -> SpMV -> compressed
all-to-all) executes for real, and compares the four wire plans.

    PYTHONPATH=src python examples/distributed_bfs.py --grid 2x2 --scale 12

``--expand`` picks the local-expansion backend (coo / ell / hybrid; auto =
hybrid with the histogram-chosen split) and prints each block's split K
and ELL padding ratio — results are bit-identical across backends.

``--batch B`` traverses B sources at once: the frontier/parent carries
widen to (B, s) planes and every exchange moves all B planes under one
wire header and one bucket consensus.  The batched parents then feed the
betweenness-centrality accumulation from
``repro.core.centrality.tree_betweenness`` (Brandes-style dependency pass
over each source's BFS tree) — the workload family multi-source batching
opens up.
"""

import argparse
import os
import time

ap = argparse.ArgumentParser()
ap.add_argument("--grid", default="2x2")
ap.add_argument("--scale", type=int, default=12)
ap.add_argument("--policy", default="top_down",
                choices=["top_down", "bottom_up", "direction_opt"],
                help="traversal direction policy (paper §3.1)")
ap.add_argument("--batch", type=int, default=1,
                help="number of BFS sources traversed simultaneously (B)")
ap.add_argument("--expand", default="coo",
                choices=["coo", "ell", "hybrid", "auto"],
                help="local-expansion backend (auto = hybrid with the "
                     "histogram-chosen split)")
args = ap.parse_args()
ROWS, COLS = (int(x) for x in args.grid.split("x"))
# only the CPU platform's device count: on a TPU the grid is built from
# the chips themselves
os.environ.setdefault(
    "XLA_FLAGS", f"--xla_force_host_platform_device_count={ROWS * COLS}"
)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import bfs as bfsmod  # noqa: E402
from repro.core import csr as csrmod  # noqa: E402
from repro.core import distributed_bfs as dbfs  # noqa: E402
from repro.core import expand as expand_mod  # noqa: E402
from repro.core import validate  # noqa: E402
from repro.core.centrality import tree_betweenness  # noqa: E402
from repro.graphgen import builder, kronecker  # noqa: E402
from repro.launch import runtime  # noqa: E402


def main() -> None:
    runtime.enable_compile_cache()
    mesh = runtime.grid_mesh(ROWS, COLS)
    g = builder.build_csr(kronecker.kronecker_edges(args.scale, seed=3), n=1 << args.scale)
    bg = csrmod.partition_2d(g, rows=ROWS, cols=COLS)
    deg = g.degrees()
    # same hub-root convention as the benchmark's acceptance rows
    roots = bfsmod.hub_roots(deg, args.batch).astype(np.int32)
    root_arg = jnp.int32(int(roots[0])) if args.batch == 1 else jnp.asarray(roots)
    print(f"grid {ROWS}x{COLS}, n={g.n:,} (padded {bg.part.n:,}), m={g.m:,}, "
          f"chunk s={bg.part.chunk:,}, e_cap={bg.e_cap:,}, "
          f"batch B={args.batch} roots={roots.tolist()} expand={args.expand}")
    backend = expand_mod.resolve(args.expand)
    for d in backend.describe(bg):
        residue = (f" residue_edges={d['residue_edges']:,}"
                   if "residue_edges" in d else "")
        print(f"  block {tuple(d['block'])}: split_k={d['split_k']} "
              f"ell_padding_ratio={d['padding_ratio']:.3f}{residue}")

    refs = {int(r): validate.reference_bfs(g, int(r)) for r in roots}
    last = None
    for mode in ("raw", "bitmap", "auto", "btfly"):
        cfg = dbfs.DistBFSConfig(mode=mode, policy=args.policy,
                                 expand=args.expand)
        fn = dbfs.build_bfs(mesh, bg, cfg)
        blocks = dbfs.shard_blocked(mesh, bg, cfg)
        parent, level, depth = fn(*blocks, root_arg)
        jax.block_until_ready(parent)
        t0 = time.perf_counter()
        parent, level, depth = fn(*blocks, root_arg)
        jax.block_until_ready(parent)
        dt = time.perf_counter() - t0
        parent_np = np.atleast_2d(np.asarray(parent))[:, : g.n]
        level_np = np.atleast_2d(np.asarray(level))[:, : g.n]
        ok = all(
            np.array_equal(level_np[k], refs[int(r)])
            for k, r in enumerate(roots)
        )
        valid = all(
            validate.validate_bfs_tree(g, parent_np[k], int(r)).ok
            for k, r in enumerate(roots)
        )
        print(f"  mode={mode:7s} policy={args.policy:13s} depth={int(depth):2d} "
              f"time={dt:.3f}s levels_match={ok} graph500_valid={valid} "
              f"({dt / args.batch:.3f}s/source)")
        last = (parent_np, level_np)

    if args.batch > 1 and last is not None:
        parent_np, level_np = last
        bc = tree_betweenness(parent_np, level_np, g.n)
        top = np.argsort(-bc)[:5]
        print(f"\nbetweenness accumulation over {args.batch} batched sources "
              "(tree-dependency approximation):")
        for v in top:
            print(f"  vertex {int(v):>8d}  degree {int(deg[v]):>6d}  "
                  f"centrality {bc[v]:,.0f}")


if __name__ == "__main__":
    main()
