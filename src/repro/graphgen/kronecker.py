"""Graph500 Kronecker (RMAT) edge-list generator (paper §2.7.1).

The Graph 500 specification generates a scale-free graph by recursively
sampling quadrants of the adjacency matrix with probabilities
(A, B, C, D) = (0.57, 0.19, 0.19, 0.05).  ``vertices = 2**scale`` and
``edges = vertices * edgefactor`` (edgefactor 16 per the benchmark).

This is a vectorized numpy implementation: one pass per scale bit over the
whole edge array, identical in distribution to the reference implementation's
per-edge recursion.  Vertex labels are randomly permuted afterwards, as the
spec requires, so that vertex id carries no locality information (the paper's
"vertex sorting" optimization then *re-introduces* locality deliberately —
see :func:`repro.graphgen.builder.relabel_by_degree`).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# Graph500 quadrant probabilities.
A, B, C, D = 0.57, 0.19, 0.19, 0.05


def _rmat_bits(seed: int, scale: int, m: int, a: float, b: float, c: float,
               lo: int, hi: int) -> np.ndarray:
    """Endpoints of edges ``[lo, hi)``: bit ``ib`` of the row draws the
    ``2 * ib``-th block of ``m`` uniforms, the column the next block, so a
    chunk reads its slice of each block from an advanced copy of the
    stream and the result does not depend on how edges are chunked."""
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ij = np.zeros((2, hi - lo), dtype=np.int64)
    for ib in range(scale):
        draws = []
        for block in (2 * ib, 2 * ib + 1):
            bitgen = np.random.PCG64(seed)
            bitgen.advance(block * m + lo)
            draws.append(np.random.Generator(bitgen).random(hi - lo))
        ii_bit = draws[0] > ab
        jj_bit = draws[1] > np.where(ii_bit, c_norm, a_norm)
        ij[0] += (1 << ib) * ii_bit
        ij[1] += (1 << ib) * jj_bit
    return ij


def _rmat(scale: int, edgefactor: int, seed: int, a: float, b: float,
          c: float) -> tuple[np.ndarray, np.random.Generator]:
    """(2, m) RMAT endpoints, and the seed's generator advanced past the
    bit draws (for the permutations that follow them).  Disjoint edge
    chunks draw in threads; the output equals drawing each bit's ``m``
    uniforms in turn from one stream."""
    m = (1 << scale) * edgefactor
    chunk = 1 << 20
    with ThreadPoolExecutor(min(32, os.cpu_count() or 1)) as pool:
        parts = pool.map(
            lambda lo: _rmat_bits(seed, scale, m, a, b, c, lo, min(lo + chunk, m)),
            range(0, m, chunk),
        )
        ij = np.concatenate(list(parts), axis=1)
    rng = np.random.default_rng(seed)
    rng.bit_generator.advance(2 * scale * m)
    return ij, rng


def kronecker_edges(
    scale: int,
    edgefactor: int = 16,
    seed: int = 1,
    permute: bool = True,
) -> np.ndarray:
    """Return an int64 array of shape (m, 2) of directed edge endpoints.

    Follows the Graph 500 octave reference: per bit, choose the row/column
    half independently with the RMAT skew, then permute vertex labels and
    shuffle edge order.
    """
    ij, rng = _rmat(scale, edgefactor, seed, A, B, C)
    if permute:
        perm = rng.permutation(1 << scale)
        ij = perm[ij]
        ij = ij[:, rng.permutation(ij.shape[1])]
    return ij.T.copy()


def rmat_edges(
    scale: int,
    edgefactor: int = 16,
    seed: int = 1,
    a: float = A,
    b: float = B,
    c: float = C,
    permute: bool = True,
) -> np.ndarray:
    """General RMAT with tunable skew (used by benchmarks to vary gap entropy)."""
    ij, rng = _rmat(scale, edgefactor, seed, a, b, c)
    if permute:
        perm = rng.permutation(1 << scale)
        ij = perm[ij]
    return ij.T.copy()
