"""ELL SpMV (frontier expansion, the XLA gather formulation every backend
runs) vs a python loop oracle — shape/density sweeps."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.bitpack import ref as bpref
from repro.kernels.spmv import ref


def _python_oracle(nbr, bits, n_cols):
    out = np.full(nbr.shape[0], ref.INF, np.int64)
    for r in range(nbr.shape[0]):
        for d in range(nbr.shape[1]):
            v = nbr[r, d]
            if v < n_cols and bits[v]:
                out[r] = min(out[r], v)
    return out


@pytest.mark.parametrize("n_rows,max_deg", [(1024, 8), (2048, 16), (1024, 32)])
@pytest.mark.parametrize("density", [0.0, 0.05, 0.5])
def test_spmv_kernel_sweep(n_rows, max_deg, density):
    n_cols = 4096
    rng = np.random.default_rng(n_rows + max_deg)
    nbr = rng.integers(0, n_cols, size=(n_rows, max_deg)).astype(np.int32)
    nbr[rng.random((n_rows, max_deg)) < 0.3] = n_cols  # padding
    bits = rng.random(n_cols) < density
    f_words = bpref.pack(jnp.asarray(bits.astype(np.uint32)), 1)
    expect = _python_oracle(nbr, bits, n_cols)
    np.testing.assert_array_equal(
        np.asarray(ref.spmv_min(jnp.asarray(nbr), f_words, n_cols)), expect
    )


@pytest.mark.parametrize("n_rows,max_deg", [(1024, 8), (2048, 16)])
@pytest.mark.parametrize("density,unreached_frac", [(0.05, 0.5), (0.5, 0.1), (0.5, 1.0)])
def test_spmv_pull_kernel_sweep(n_rows, max_deg, density, unreached_frac):
    """Pull direction: unreached rows probe the frontier bitmap, finished
    rows are masked to INF — XLA formulation vs python loop."""
    n_cols = 4096
    rng = np.random.default_rng(n_rows * max_deg + int(100 * density))
    nbr = rng.integers(0, n_cols, size=(n_rows, max_deg)).astype(np.int32)
    nbr[rng.random((n_rows, max_deg)) < 0.3] = n_cols  # padding
    bits = rng.random(n_cols) < density
    unreached = rng.random(n_rows) < unreached_frac
    f_words = bpref.pack(jnp.asarray(bits.astype(np.uint32)), 1)
    u_words = bpref.pack(jnp.asarray(unreached.astype(np.uint32)), 1)
    expect = np.where(unreached, _python_oracle(nbr, bits, n_cols), ref.INF)
    np.testing.assert_array_equal(
        np.asarray(ref.spmv_pull_min(jnp.asarray(nbr), f_words, u_words, n_cols)),
        expect,
    )


@pytest.mark.parametrize("density", [0.0, 0.05, 0.5])
def test_spmv_planes_kernel_sweep(density):
    """Multi-source expansion: the plane-batched push/pull entry points equal
    the python oracle applied per source plane."""
    n_rows, max_deg, n_cols, b = 1024, 16, 4096, 3
    rng = np.random.default_rng(int(density * 100) + 7)
    nbr = rng.integers(0, n_cols, size=(n_rows, max_deg)).astype(np.int32)
    nbr[rng.random((n_rows, max_deg)) < 0.3] = n_cols  # padding
    bits = rng.random((b, n_cols)) < density
    unreached = rng.random((b, n_rows)) < 0.5
    f_words = jnp.stack(
        [bpref.pack(jnp.asarray(p.astype(np.uint32)), 1) for p in bits]
    )
    u_words = jnp.stack(
        [bpref.pack(jnp.asarray(p.astype(np.uint32)), 1) for p in unreached]
    )
    expect_push = np.stack([_python_oracle(nbr, p, n_cols) for p in bits])
    np.testing.assert_array_equal(
        np.asarray(ref.spmv_min_planes(jnp.asarray(nbr), f_words, n_cols)),
        expect_push,
    )
    expect_pull = np.where(unreached, expect_push, ref.INF)
    np.testing.assert_array_equal(
        np.asarray(
            ref.spmv_pull_min_planes(jnp.asarray(nbr), f_words, u_words, n_cols)
        ),
        expect_pull,
    )


def test_spmv_pull_all_reached_is_inf():
    """With every row reached the pull produces no candidates at all."""
    n_rows = n_cols = 1024
    nbr = np.zeros((n_rows, 8), np.int32)  # everyone neighbors vertex 0
    f_words = bpref.pack(jnp.ones(n_cols, jnp.uint32), 1)  # full frontier
    u_words = bpref.pack(jnp.zeros(n_rows, jnp.uint32), 1)  # nobody unreached
    out = np.asarray(ref.spmv_pull_min(jnp.asarray(nbr), f_words, u_words, n_cols))
    assert (out == ref.INF).all()


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1 << 16))
def test_spmv_matches_segment_min_formulation(seed):
    """The ELL expansion agrees with the segment_min edge-centric formulation
    used by core/bfs.py (same semiring, different data structure)."""
    import jax

    rng = np.random.default_rng(seed)
    n_rows = 1024
    n_cols = 2048
    m = int(rng.integers(1, 4000))
    src = rng.integers(0, n_cols, m).astype(np.int32)
    dst = rng.integers(0, n_rows, m).astype(np.int32)
    bits = rng.random(n_cols) < 0.2
    # edge-centric reference
    cand = np.where(bits[src], src, ref.INF)
    seg = np.full(n_rows, ref.INF, np.int64)
    np.minimum.at(seg, dst, cand)
    # ELL slab (max_deg covers the densest row)
    max_deg = int(np.bincount(dst, minlength=n_rows).max())
    ell = ref.ell_from_coo(jnp.asarray(src), jnp.asarray(dst), n_rows, n_cols, max_deg)
    f_words = bpref.pack(jnp.asarray(bits.astype(np.uint32)), 1)
    out = np.asarray(ref.spmv_min(ell, f_words, n_cols))
    np.testing.assert_array_equal(out, seg)
