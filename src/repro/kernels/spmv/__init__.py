"""Blocked ELL SpMV: the BFS frontier-expansion hot spot.

The paper's §6 hand-optimizes exactly this loop with CPU SIMD (strength
reduction, vectorization of the matrix iteration).  Here the
destination-major ELL neighbor slab probes the packed frontier bitmap
through XLA's gather and min-reduces candidate parents per row, in both
directions (Beamer, paper §3.1): push, and pull, where only unreached
rows produce candidates.  See :mod:`repro.kernels.spmv.ref` for why this
is not a Pallas kernel.
"""

from repro.kernels.spmv import ref  # noqa: F401
