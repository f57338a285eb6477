"""Pallas kernels vs pure-jnp oracles: shape x width x dtype sweeps
(interpret mode executes the kernel body on CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.bitpack import bitpack, ops as bpops, ref as bpref
from repro.kernels.popcount import ops as pcops, popcount, ref as pcref
from repro.kernels.quant import ops as qops, quant, ref as qref


@pytest.mark.parametrize("b", bpref.B_CLASSES)
@pytest.mark.parametrize("n_blocks", [1, 2, 5])
def test_bitpack_pallas_matches_ref(b, n_blocks):
    n = n_blocks * bitpack.VALS_PER_BLOCK
    rng = np.random.default_rng(b * 100 + n_blocks)
    hi = (1 << b) if b < 32 else (1 << 32)
    vals = rng.integers(0, hi, size=n, dtype=np.uint64).astype(np.uint32)
    v = jnp.asarray(vals)
    ref_words = bpref.pack(v, b)
    pal_words = bitpack.pack_pallas(v, b)
    np.testing.assert_array_equal(np.asarray(pal_words), np.asarray(ref_words))
    np.testing.assert_array_equal(np.asarray(bitpack.unpack_pallas(pal_words, b)), vals)
    np.testing.assert_array_equal(np.asarray(bpref.unpack(ref_words, b)), vals)


@settings(max_examples=20, deadline=None)
@given(
    b=st.sampled_from(bpref.B_CLASSES),
    count=st.integers(0, 4096),
    seed=st.integers(0, 1 << 16),
)
def test_sorted_id_stream_roundtrip_property(b, count, seed):
    """Fused delta+pack/unpack+cumsum is exact for any sorted stream whose
    gaps fit the width class."""
    cap = 4096
    rng = np.random.default_rng(seed)
    max_gap = (1 << b) - 1 if b < 32 else (1 << 20)
    gaps = rng.integers(0, max(max_gap, 1) + 1, size=count)
    ids = np.cumsum(gaps).astype(np.int32)
    padded = np.zeros(cap, np.int32)
    padded[:count] = ids
    words = bpops.pack_sorted_ids(jnp.asarray(padded), jnp.int32(count), b)
    back = bpops.unpack_sorted_ids(words, jnp.int32(count), b, fill=-1)
    np.testing.assert_array_equal(np.asarray(back)[:count], ids)
    assert np.all(np.asarray(back)[count:] == -1)


def test_required_width_class():
    gaps = jnp.asarray(np.array([0, 1, 3], np.uint32))
    assert bpref.B_CLASSES[int(bpref.required_width_class(gaps))] == 2
    gaps = jnp.asarray(np.array([0, 300], np.uint32))
    assert bpref.B_CLASSES[int(bpref.required_width_class(gaps))] == 16


@pytest.mark.parametrize("rows", [1, 3])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_quant_pallas_matches_ref(rows, dtype):
    n = rows * quant.ROWS * qref.GROUP
    rng = np.random.default_rng(rows)
    x = (rng.normal(size=n) * 10).astype(dtype)
    q_ref, s_ref = qref.quantize(jnp.asarray(x))
    q_pal, s_pal = quant.quantize_pallas(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(q_pal), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref), rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1 << 16), scale=st.floats(1e-3, 1e3))
def test_quant_error_bound_property(seed, scale):
    """Dequantized values are within scale/2 = maxabs/254 per 128-group."""
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=qref.GROUP * 4) * scale).astype(np.float32)
    q, s = qref.quantize(jnp.asarray(x))
    xd = np.asarray(qref.dequantize(q, s))
    bound = np.repeat(np.asarray(s), qref.GROUP) / 2 + 1e-12
    assert np.all(np.abs(xd - x) <= bound)


def test_popcount_matches_python():
    rng = np.random.default_rng(0)
    words = rng.integers(0, 1 << 32, size=2048, dtype=np.uint64).astype(np.uint32)
    expect = np.array([bin(int(w)).count("1") for w in words])
    np.testing.assert_array_equal(np.asarray(pcref.popcount_words(jnp.asarray(words))), expect)
    total = np.asarray(popcount.popcount_planes_pallas(jnp.asarray(words[None])))
    np.testing.assert_array_equal(total, [expect.sum()])
    np.testing.assert_array_equal(
        np.asarray(pcops.popcount_planes(jnp.asarray(words[None]))), [expect.sum()]
    )


def test_popcount_planes_matches_per_plane_blocks():
    """The plane-blocked kernel (one grid over B x word tiles, each plane's
    lane partials accumulated across its tiles) equals the per-plane sum of
    per-word counts, including the zero-padding path for word counts off
    the 1024-word block."""
    rng = np.random.default_rng(1)
    # 3072 words = 24 rows of 128 -> three 8-row steps per plane
    words = rng.integers(0, 1 << 32, size=(3, 3072), dtype=np.uint64).astype(np.uint32)
    totals = np.asarray(pcref.popcount_words(jnp.asarray(words))).sum(axis=1)
    np.testing.assert_array_equal(
        np.asarray(popcount.popcount_planes_pallas(jnp.asarray(words))), totals
    )
    np.testing.assert_array_equal(
        np.asarray(pcops.popcount_planes(jnp.asarray(words))), totals
    )
    # unaligned word count -> ops pads each plane to the block geometry
    np.testing.assert_array_equal(
        np.asarray(pcops.popcount_planes(jnp.asarray(words[:, :100]))),
        np.stack([np.asarray(pcref.popcount_words(jnp.asarray(p))).sum() for p in words[:, :100]]),
    )


@pytest.mark.parametrize("b", [1, 16])
def test_bitpack_planes_roundtrip(b):
    """(B, n) plane matrices pack/unpack through the chunk-aligned flatten
    losslessly — the layout the multi-source frontier bitmaps ride."""
    n = 2 * bitpack.VALS_PER_BLOCK
    rng = np.random.default_rng(b)
    hi = 1 << b
    vals = rng.integers(0, hi, size=(3, n), dtype=np.uint64).astype(np.uint32)
    words = bpops.pack_planes(jnp.asarray(vals), b)
    assert words.shape == (3, n * b // 32)
    per_plane = np.stack([np.asarray(bpref.pack(jnp.asarray(p), b)) for p in vals])
    np.testing.assert_array_equal(np.asarray(words), per_plane)
    np.testing.assert_array_equal(np.asarray(bpops.unpack_planes(words, b)), vals)


def test_compact_ids():
    mask = jnp.asarray(np.array([0, 1, 1, 0, 1, 0, 0, 1], bool))
    ids, count = bpops.compact_ids(mask, capacity=8, fill=8)
    assert int(count) == 4
    np.testing.assert_array_equal(np.asarray(ids)[:4], [1, 2, 4, 7])
    assert np.all(np.asarray(ids)[4:] == 8)


@pytest.mark.parametrize("density,capacity", [(0.0, 64), (0.01, 64), (0.3, 256), (0.9, 4096)])
def test_compact_ids_matches_nonzero(density, capacity):
    """The scan-and-search compaction equals ``jnp.nonzero`` with a static
    size, truncation past the capacity included."""
    rng = np.random.default_rng(int(density * 100) + capacity)
    mask = rng.random(4096) < density
    ids, count = bpops.compact_ids(jnp.asarray(mask), capacity=capacity, fill=4096)
    (want,) = np.nonzero(mask)
    want = np.concatenate([want, np.full(capacity, 4096)])[:capacity]
    assert int(count) == mask.sum()
    np.testing.assert_array_equal(np.asarray(ids), want)


@pytest.mark.parametrize("b", [1, 4, 16])
def test_bitpack_ops_pad_to_kernel_alignment(monkeypatch, b):
    """The TPU branch of the dispatch packs streams off the kernel's
    4096-value alignment by padding whole chunks, never by falling back to
    the jnp reference (the kernel runs in interpret mode here)."""
    monkeypatch.setattr(bpops, "_use_pallas", lambda: True)
    monkeypatch.setattr(bpref, "pack", None)  # the reference must not run
    n = 3 * bpref.CHUNK
    rng = np.random.default_rng(b)
    vals = rng.integers(0, 1 << b, size=n, dtype=np.uint64).astype(np.uint32)
    words = bpops.pack(jnp.asarray(vals), b)
    assert words.shape == (n * b // 32,)
    np.testing.assert_array_equal(np.asarray(bpops.unpack(words, b)), vals)
    monkeypatch.undo()
    np.testing.assert_array_equal(
        np.asarray(words), np.asarray(bpref.pack(jnp.asarray(vals), b))
    )


def test_quant_ops_pad_to_kernel_step(monkeypatch):
    """The TPU branch quantizes payloads off the kernel's ROWS x GROUP step
    by zero-padding, and matches the oracle on the real values."""
    monkeypatch.setattr(qops, "_use_pallas", lambda: True)
    x = (np.random.default_rng(3).normal(size=5 * qref.GROUP) * 4).astype(np.float32)
    q, s = qops.quantize(jnp.asarray(x))
    q_ref, s_ref = qref.quantize(jnp.asarray(x))
    assert q.shape == q_ref.shape and s.shape == s_ref.shape
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-6)


def test_popcount_ops_kernel_branch_pads(monkeypatch):
    """The TPU branch of ``popcount_planes`` pads planes off the 1024-word
    block and runs the kernel."""
    monkeypatch.setattr(pcops, "_use_pallas", lambda: True)
    words = np.random.default_rng(4).integers(
        0, 1 << 32, size=(2, 1500), dtype=np.uint64
    ).astype(np.uint32)
    np.testing.assert_array_equal(
        np.asarray(pcops.popcount_planes(jnp.asarray(words))),
        np.asarray(pcref.popcount_words(jnp.asarray(words))).sum(axis=1),
    )
