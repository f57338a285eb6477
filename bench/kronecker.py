"""The benchmark's own Graph500 inputs: a Kronecker edge list made on the
device from ``--seed``, and the search keys (roots) drawn from it.

The generator follows the Graph500 reference (the octave ``kronecker_generator``
of the specification): per scale bit, the row half is drawn against
``A + B`` and the column half against the normalised ``C`` or ``A``
probability, then vertex labels are permuted and the edge order is
shuffled.  It runs as one jitted call on the device, so a run spends no
host time drawing 2 * scale * M uniforms; the same seed gives the same
edges on every backend (threefry keys).  Root sampling copies the
program's ``benchmarks.teps.valid_roots`` rule: valid roots are vertices
with an edge other than a self-loop, drawn without replacement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: Graph500 quadrant probabilities (D = 1 - A - B - C), the defaults
A, B, C = 0.57, 0.19, 0.19


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words for ``stream`` of ``seed``: any whole seed, also one
    wider than 32 bits, maps to independent streams."""
    return np.random.SeedSequence([seed, stream]).generate_state(2, np.uint32)


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _kronecker(key_data, scale: int, edgefactor: int, a: float, b: float, c: float):
    n = 1 << scale
    m = n * edgefactor
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
    k_bits, k_perm, k_shuffle = jax.random.split(key, 3)

    def bit(ib, ij):
        u = jax.random.uniform(jax.random.fold_in(k_bits, ib), (2, m))
        ii = u[0] > ab
        jj = u[1] > jnp.where(ii, c_norm, a_norm)
        step = jnp.left_shift(jnp.int32(1), ib)
        return ij + step * jnp.stack([ii, jj]).astype(jnp.int32)

    ij = jax.lax.fori_loop(0, scale, bit, jnp.zeros((2, m), jnp.int32))
    label = jax.random.permutation(k_perm, n).astype(jnp.int32)
    ij = label[ij]
    order = jax.random.bits(k_shuffle, (m,), jnp.uint32)
    _, u, v = jax.lax.sort((order, ij[0], ij[1]), num_keys=1)
    return jnp.stack([u, v], axis=1)


def kronecker_edges(seed: int, scale: int, edgefactor: int,
                    a: float = A, b: float = B, c: float = C) -> np.ndarray:
    """``(M, 2)`` int32 host array of the Graph500 input tuples,
    ``M = edgefactor * 2**scale``, made on the default device."""
    out = _kronecker(seed_words(seed, 0), scale, edgefactor, a, b, c)
    edges = np.asarray(out)
    out.delete()
    return edges


def degrees(edges: np.ndarray, n: int) -> np.ndarray:
    """Undirected degree of each vertex, self-loops left out."""
    u, v = edges[:, 0], edges[:, 1]
    keep = u != v
    return np.bincount(u[keep], minlength=n) + np.bincount(v[keep], minlength=n)


def valid_roots(deg: np.ndarray, count: int, seed: int) -> np.ndarray:
    """Graph500 search keys: ``count`` vertices with an edge, drawn
    uniformly without replacement from the seed."""
    cand = np.flatnonzero(deg > 0)
    if cand.size < count:
        raise ValueError(f"only {cand.size} vertices have an edge; "
                         f"cannot draw {count} distinct roots")
    return seed_rng(seed, 1).choice(cand, size=count, replace=False).astype(np.int32)
