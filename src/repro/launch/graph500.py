"""Graph500 kernel 2 on the distributed 2D driver: the steps that the
example harness (``examples/graph500_benchmark.py``) and the chip smoke run
(``chip_smoke.py``) share.

Set-up runs on the host: Kronecker generation, CSR construction and the 2D
partition.  :class:`Search` then places the blocks on a grid mesh and runs
batches of roots through :func:`repro.core.distributed_bfs.build_bfs`;
:func:`check_trees` applies the Graph500 validation to every tree.
"""

from __future__ import annotations

import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import csr as csrmod
from repro.core import distributed_bfs as dbfs
from repro.core import validate
from repro.graphgen import builder, kronecker


@dataclasses.dataclass(frozen=True)
class Graph:
    g: builder.CSRGraph
    bg: csrmod.BlockedGraph
    gen_s: float  # Kronecker generation (host)
    csr_s: float  # kernel 1: CSR construction (host)
    partition_s: float  # 2D partition (host)


def build_graph(scale: int, edgefactor: int, rows: int, cols: int,
                seed: int = 1) -> Graph:
    """Generate, build and partition a Graph500 Kronecker graph."""
    t0 = time.perf_counter()
    edges = kronecker.kronecker_edges(scale, edgefactor, seed=seed)
    t1 = time.perf_counter()
    g = builder.build_csr(edges, n=1 << scale)
    del edges
    t2 = time.perf_counter()
    bg = csrmod.partition_2d(g, rows=rows, cols=cols)
    t3 = time.perf_counter()
    return Graph(g=g, bg=bg, gen_s=t1 - t0, csr_s=t2 - t1, partition_s=t3 - t2)


class Search:
    """One compiled driver configuration with its blocks on the mesh."""

    def __init__(self, mesh, bg: csrmod.BlockedGraph, cfg: dbfs.DistBFSConfig):
        self.n = bg.part.n_orig
        self.fn = dbfs.build_bfs(mesh, bg, cfg)
        self.blocks = dbfs.shard_blocked(mesh, bg, cfg)

    @staticmethod
    def _arg(roots):
        return jnp.int32(roots) if np.ndim(roots) == 0 else jnp.asarray(roots)

    def compile(self, roots) -> float:
        """Compile the program for calls shaped like ``roots``; returns
        the seconds it took (later calls of that shape reuse it)."""
        t0 = time.perf_counter()
        self.fn.lower_call(*self.blocks, self._arg(roots)).compile()
        return time.perf_counter() - t0

    def __call__(self, roots):
        """BFS from ``roots`` (a ``(B,)`` array, or one int for a B=1
        call); returns host ``(B, n)`` parent and level planes, the depth
        and the wall time up to ``block_until_ready``."""
        arg = self._arg(roots)
        t0 = time.perf_counter()
        parent, level, depth = self.fn(*self.blocks, arg)
        jax.block_until_ready((parent, level, depth))
        dt = time.perf_counter() - t0
        parent = np.atleast_2d(np.asarray(parent))[:, : self.n]
        level = np.atleast_2d(np.asarray(level))[:, : self.n]
        return parent, level, int(depth), dt

    def release(self) -> None:
        """Free the blocks' device memory before the next configuration."""
        for a in self.blocks:
            a.delete()


def check_trees(g: builder.CSRGraph, roots, parent, level) -> None:
    """Graph500-validate every tree of a batch; raise on the first failure.

    The trees validate in threads: the checks are whole-array numpy
    passes that release the GIL."""
    roots = np.atleast_1d(roots)

    def one(k):
        return validate.validate_bfs_tree(g, parent[k], int(roots[k]), level[k])

    with ThreadPoolExecutor(len(roots)) as pool:
        results = list(pool.map(one, range(len(roots))))
    for root, v in zip(roots, results):
        if not v.ok:
            raise AssertionError(f"root {int(root)}: {v.failures}")
