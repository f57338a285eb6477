"""Multi-device distributed-BFS correctness check (run with forced host devices).

Usage: XLA_FLAGS=--xla_force_host_platform_device_count=4 python scripts/check_dist_bfs.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import csr as csrmod
from repro.core import distributed_bfs as dbfs
from repro.core import validate
from repro.graphgen import builder, kronecker


def main() -> None:
    scale = 10
    g = builder.build_csr(kronecker.kronecker_edges(scale, seed=3), n=1 << scale)
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    bg = csrmod.partition_2d(g, rows=2, cols=2)
    print(f"n={g.n} padded={bg.part.n} m_sym={g.m} e_cap={bg.e_cap} s={bg.part.chunk}")

    ref_levels = validate.reference_bfs(g, root=0)
    # wire modes (top_down) plus every traversal policy on the adaptive
    # plan; the low alpha forces direction_opt through its pull branch
    combos = [(m, "top_down", None) for m in ("raw", "bitmap", "auto", "btfly")]
    combos += [(m, p, 0.01) for m in ("auto", "btfly")
               for p in ("bottom_up", "direction_opt")]
    for mode, policy, alpha in combos:
        cfg = dbfs.DistBFSConfig(mode=mode, policy=policy, alpha=alpha)
        fn = dbfs.build_bfs(mesh, bg, cfg)
        blocks = dbfs.shard_blocked(mesh, bg, cfg)
        parent, level, depth = fn(*blocks, jnp.int32(0))
        parent = np.asarray(parent)[: g.n]
        level = np.asarray(level)[: g.n]
        assert np.array_equal(level, ref_levels), (
            mode,
            policy,
            np.nonzero(level != ref_levels)[0][:10],
        )
        res = validate.validate_bfs_tree(g, parent, root=0, level=level)
        assert res.ok, (mode, policy, res.failures)
        print(f"mode={mode:7s} policy={policy:13s} OK "
              f"depth={int(depth)} reached={res.n_reached}")
    print("DIST BFS ALL MODES OK")


if __name__ == "__main__":
    main()
