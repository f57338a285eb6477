"""Static-shape compressed-stream codec + bucket ladder (single-device parts;
the collective paths are covered by tests/test_dist.py subprocesses)."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import comm
from repro import comm as cc


@settings(max_examples=25, deadline=None)
@given(count=st.integers(0, 2048), seed=st.integers(0, 1 << 16))
def test_id_stream_roundtrip_property(count, seed):
    """PFOR-16 with static exception slots is exact for any sorted stream
    (large gaps land in the exception area)."""
    cap = 2048
    rng = np.random.default_rng(seed)
    # mixture of small gaps and occasional huge ones (> 2^16)
    gaps = rng.integers(0, 300, size=count)
    huge = rng.random(count) < 0.02
    gaps = np.where(huge, rng.integers(1 << 16, 1 << 24, size=count), gaps)
    ids = np.cumsum(gaps).astype(np.int32)
    padded = np.zeros(cap, np.int32)
    padded[:count] = ids
    spec = cc.IdStreamSpec(cap=cap, width=16)
    n_exc = int((gaps >> 16 > 0).sum())
    if n_exc > spec.exc_cap:
        return  # bucket selection would reject this stream
    words, meta = cc.pack_id_stream(jnp.asarray(padded), jnp.int32(count), spec)
    assert words.shape[0] == spec.n_words
    out, out_count = cc.unpack_id_stream(words, meta, spec, fill=-1)
    assert int(out_count) == count
    np.testing.assert_array_equal(np.asarray(out)[:count], ids)


def test_bitmap_roundtrip():
    rng = np.random.default_rng(0)
    bits = jnp.asarray(rng.random(4096) < 0.3)
    words = cc.pack_bitmap(bits)
    assert words.shape[0] == 4096 // 32
    np.testing.assert_array_equal(np.asarray(cc.unpack_bitmap(words)), np.asarray(bits))


def test_bucket_ladder_sizes_and_selection():
    s = 1 << 16
    ladder = cc.BucketLadder.default(s)
    assert ladder.n_branches >= 2
    # word counts ascend; bitmap is the fallback floor
    sizes = [ladder.words_for_branch(i) for i in range(ladder.n_branches)]
    assert sizes[-1] == s // 32
    assert all(a < b for a, b in zip(sizes[:-1], sizes[1:])), sizes
    # sparse frontier -> smallest bucket; dense -> bitmap
    assert int(ladder.bucket_for(jnp.int32(10), jnp.int32(0))) == 0
    assert int(ladder.bucket_for(jnp.int32(s), jnp.int32(0))) == len(ladder.specs)
    # exception overflow forces escalation
    assert int(
        ladder.bucket_for(jnp.int32(10), jnp.int32(ladder.specs[0].exc_cap + 1))
    ) > 0


def test_compressed_words_beat_bitmap_beat_raw():
    """The three wire formats order as paper predicts: packed << bitmap << raw."""
    s = 1 << 16
    ladder = cc.BucketLadder.default(s)
    raw_words = s  # 32-bit id slots
    bitmap_words = s // 32
    sparse_words = ladder.specs[0].n_words
    assert sparse_words < bitmap_words < raw_words
    # data reduction vs raw exceeds the paper's 90% once sparse bucket hits
    assert 1 - sparse_words / raw_words > 0.90


# ---------------------------------------------------------------------------
# geometry-boundary round trips + engine bucket choice (repro.comm)
# ---------------------------------------------------------------------------


def test_id_stream_roundtrip_count_at_cap():
    """count == cap: every slot carries a real id, none spill."""
    cap = 1024
    spec = comm.IdStreamSpec(cap=cap)
    ids = (np.arange(cap, dtype=np.int64) * 3 + 1).astype(np.int32)  # no exceptions
    words, meta = comm.pack_id_stream(jnp.asarray(ids), jnp.int32(cap), spec)
    assert int(meta[0]) == cap and int(meta[1]) == 0
    out, count = comm.unpack_id_stream(words, meta, spec, fill=-1)
    assert int(count) == cap
    np.testing.assert_array_equal(np.asarray(out), ids)


def test_id_stream_roundtrip_exceptions_at_cap():
    """exc_count == exc_cap: the exception area is exactly full."""
    cap = 1024
    spec = comm.IdStreamSpec(cap=cap)
    count = 256
    gaps = np.ones(count, np.int64)
    gaps[:spec.exc_cap] = 1 << 17  # exactly exc_cap gaps overflow 16 bits
    ids = np.cumsum(gaps).astype(np.int32)
    padded = np.zeros(cap, np.int32)
    padded[:count] = ids
    words, meta = comm.pack_id_stream(jnp.asarray(padded), jnp.int32(count), spec)
    assert int(meta[1]) == spec.exc_cap
    out, out_count = comm.unpack_id_stream(words, meta, spec, fill=-1)
    assert int(out_count) == count
    np.testing.assert_array_equal(np.asarray(out)[:count], ids)


def test_id_stream_roundtrip_empty():
    """count == 0: meta is all-zero and unpack returns only fill."""
    cap = 1024
    spec = comm.IdStreamSpec(cap=cap)
    words, meta = comm.pack_id_stream(jnp.zeros(cap, jnp.int32), jnp.int32(0), spec)
    assert int(meta[0]) == 0 and int(meta[1]) == 0
    out, count = comm.unpack_id_stream(words, meta, spec, fill=7)
    assert int(count) == 0
    assert np.all(np.asarray(out) == 7)


def test_ladder_stores_payload_width():
    """payload_width lives on the ladder: words_for_branch needs no re-pass
    and the per-bucket formats bake it in."""
    ladder = comm.BucketLadder.default(1 << 16, floor_words=1 << 16, payload_width=16)
    assert ladder.payload_width == 16
    assert len(ladder.specs) >= 2
    for i, f in enumerate(ladder.formats()):
        assert f.payload_width == 16
        assert ladder.words_for_branch(i) == f.data_words
    # the payload makes every bucket strictly wider than the payload-free one
    bare = comm.BucketLadder.default(1 << 16, floor_words=1 << 16)
    for i in range(min(len(ladder.specs), len(bare.specs))):
        assert ladder.words_for_branch(i) > bare.words_for_branch(i)


def test_bucket_choice_monotone_in_count_and_exceptions():
    """Ladder bucket choice is monotone: more ids (or more exceptions)
    never selects a smaller capacity class."""
    s = 1 << 16
    ladder = comm.BucketLadder.default(s, floor_words=s)
    assert len(ladder.specs) >= 2
    prev = 0
    for count in range(0, s + 1, 4096):
        b = int(ladder.bucket_for(jnp.int32(count), jnp.int32(0)))
        assert b >= prev, (count, b, prev)
        prev = b
    assert prev == len(ladder.specs)  # full count lands on the dense fallback
    prev = 0
    for exc in range(0, ladder.specs[-1].exc_cap + 2, 64):
        b = int(ladder.bucket_for(jnp.int32(10), jnp.int32(exc)))
        assert b >= prev, (exc, b, prev)
        prev = b


def test_comm_stats_moved_bytes_accounting():
    """moved_bytes records true wire traffic next to the HLO-parity nbytes:
    identity permute pairs move nothing, gathers keep their own chunk, and
    the ring all-reduce moves 2(g-1)/g of its operand."""
    stats = comm.CommStats()
    # default: moved == nbytes (host-replay adds are already true traffic)
    stats.add("zone", "fmt", "all-to-all", 100)
    stats.add("zone", "fmt", "all-to-all", 50)
    (rec,) = stats.records()
    assert rec.nbytes == 150 and rec.moved_bytes == 150
    # trace-style record with an explicit moved count
    stats.record("t", "membership", "collective-permute", "words", 8192,
                 moved_bytes=5461)
    rec = [r for r in stats.records() if r.phase == "t"][0]
    assert rec.nbytes == 8192 and rec.moved_bytes == 5461
    assert rec.hlo_bytes == 8192  # HLO parity never uses moved bytes
    assert stats.per_phase_moved()["t"] == 5461
    assert stats.total_moved_bytes == 150 + 5461
    # re-recording with a different moved count is rejected like nbytes
    with pytest.raises(ValueError):
        stats.record("t", "membership", "collective-permute", "words", 8192,
                     moved_bytes=0)


def test_engine_ppermute_identity_pairs_move_nothing():
    """An all-self-pairs transpose records full HLO operand bytes but zero
    moved bytes (the Partition2D transpose always contains self-sends)."""
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("x",))
    stats = comm.CommStats()

    def body(x):
        ex = comm.AdaptiveExchange("bfs/transpose", "x", 1, None, stats)
        return ex.ppermute(x.reshape(-1), [(0, 0)], fmt="membership")

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                              check_vma=False))
    np.testing.assert_array_equal(
        np.asarray(f(jnp.arange(64, dtype=jnp.int32))), np.arange(64)
    )
    (rec,) = stats.records()
    assert rec.collective == "collective-permute"
    assert rec.nbytes == 64 * 4 and rec.moved_bytes == 0


def test_butterfly_stage_collectives_single_rank():
    """ppermute_min_block / ppermute_membership_block round-trip on a
    single-rank axis with an identity pair (the degenerate stage): packed
    streams reconstruct the dense candidates / membership exactly."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.comm import butterfly
    from repro.comm import collectives as cc_new

    s, n = 8192, 1 << 15
    ladder, floor = butterfly.row_wire(s, n)
    assert ladder.specs, "row wire must keep sparse buckets at this geometry"
    mesh = jax.make_mesh((1,), ("x",))
    rng = np.random.default_rng(0)
    for planes in (1, 3):  # single-source wire and a multi-source plane block
        for density in (0.001, 0.02, 0.9):
            block_np = np.where(
                rng.random((2, planes, s)) < density,
                rng.integers(0, n, size=(2, planes, s)),
                np.iinfo(np.int32).max,
            ).astype(np.int32)

            def body(block, _p=planes):
                ex = comm.AdaptiveExchange("stage", "x", 1, ladder, None,
                                           planes=_p)
                return cc_new.ppermute_min_block(
                    ex, block.reshape(2, _p, s), [(0, 0)], ladder, floor,
                    gate=jnp.bool_(True),
                )

            f = jax.jit(
                jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                              check_vma=False)
            )
            out = np.asarray(f(jnp.asarray(block_np)))
            np.testing.assert_array_equal(
                out, block_np, err_msg=f"b={planes} d={density}"
            )

            bits_np = rng.random((2, planes, s)) < density
            col_ladder, _ = butterfly.unreached_wire(s)

            def body_m(bits, _p=planes):
                ex = comm.AdaptiveExchange("stage", "x", 1, col_ladder, None,
                                           planes=_p)
                return cc_new.ppermute_membership_block(
                    ex, bits.reshape(2, _p, s), [(0, 0)], col_ladder,
                    gate=jnp.bool_(True),
                )

            fm = jax.jit(
                jax.shard_map(body_m, mesh=mesh, in_specs=P(), out_specs=P(),
                              check_vma=False)
            )
            np.testing.assert_array_equal(
                np.asarray(fm(jnp.asarray(bits_np))), bits_np,
                err_msg=f"b={planes} d={density}",
            )


@pytest.mark.slow
def test_adaptive_exchange_bucket_choice_monotone():
    """End-to-end through AdaptiveExchange.dispatch: denser memberships
    dispatch to monotonically larger branches, and the consensus pmax is
    byte-accounted."""
    import functools

    import jax
    from jax.sharding import PartitionSpec as P

    s = 1 << 16
    ladder = comm.BucketLadder.default(s, floor_words=s)
    mesh = jax.make_mesh((1,), ("x",))
    stats = comm.CommStats()

    def which_branch(bits):
        ex = comm.AdaptiveExchange("test", "x", 1, ladder, stats)
        count, exc = comm.stream_stats(bits)
        branches = [
            functools.partial(lambda i, _: jnp.int32(i), i)
            for i in range(ladder.n_branches)
        ]
        return ex.dispatch(ladder.bucket_for(count, exc), branches)

    f = jax.jit(jax.shard_map(which_branch, mesh=mesh, in_specs=P(), out_specs=P()))
    rng = np.random.default_rng(0)
    prev = 0
    for density in (0.002, 0.02, 0.1, 0.6):
        b = int(f(jnp.asarray(rng.random(s) < density)))
        assert b >= prev, (density, b, prev)
        prev = b
    assert prev == len(ladder.specs)  # densest input -> dense fallback
    assert any(r.fmt == "consensus" for r in stats.records())


def test_stream_stats_match_compacted_gaps():
    """Counts and 16-bit gap exceptions from running maxima equal those of
    the compacted stream's gaps, per plane of a batch."""
    from repro.kernels.bitpack import ops as bpops
    from repro.kernels.bitpack import ref as bpref

    s = 1 << 18
    rng = np.random.default_rng(3)
    bits = np.zeros((4, s), bool)
    bits[0, [0, 70000, 70001, 200000]] = True  # first id at 0, two wide gaps
    bits[1, rng.integers(0, s, 40)] = True
    bits[2] = rng.random(s) < 0.2
    counts, excs = comm.stream_stats(jnp.asarray(bits))
    for p in range(4):
        ids, count = bpops.compact_ids(jnp.asarray(bits[p]), s, fill=s)
        gaps = np.asarray(bpref.gaps_from_sorted(ids, count))
        assert int(counts[p]) == int(count) == bits[p].sum()
        assert int(excs[p]) == int(((gaps >> 16) > 0).sum())
    assert int(excs[0]) == 2 and int(excs[3]) == 0
