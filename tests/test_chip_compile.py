"""Compile rehearsal for a TPU v5e: the main path's kernels at real widths.

Every compile here targets a described ``v5e:2x2`` topology, so the chip's
own compiler accepts or refuses each kernel without a chip attached.
Nothing runs: these tests say nothing about results or times.  The
topology is described inside a fixture (never while a module imports), and
the tests skip where it cannot be described.

``jax.default_backend()`` still reports the CPU here, so the tests steer
the dispatch layers onto their TPU branches with ``monkeypatch``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import distributed_bfs as dbfs
from repro.core import expand as expand_mod
from repro.core.csr import Partition2D
from repro.kernels.bitpack import bitpack
from repro.kernels.popcount import popcount
from repro.kernels.quant import quant

SCALE = 22  # the repo's scale22 Graph500 shape: n = 4,194,304
N = 1 << SCALE
M = 2 * 16 * N  # symmetrized edges at edgefactor 16
B = 4  # root planes per batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def tpu_dispatch(monkeypatch):
    """Take the TPU branch of every dispatch layer, and keep compiles for
    the described chip out of the persistent cache (they cannot be read
    back without a chip)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def test_popcount_planes_compiles(one_chip):
    words = jax.ShapeDtypeStruct((B, N // 32), jnp.uint32, sharding=one_chip)
    assert "tpu_custom_call" in _compile(popcount.popcount_planes_pallas, words)


@pytest.mark.parametrize("b", [1, 16, 32])
def test_bitpack_compiles(one_chip, b):
    vals = jax.ShapeDtypeStruct((B * N,), jnp.uint32, sharding=one_chip)
    words = jax.ShapeDtypeStruct((B * N * b // 32,), jnp.uint32, sharding=one_chip)
    pack = _compile(lambda v: bitpack.pack_pallas(v, b), vals)
    unpack = _compile(lambda w: bitpack.unpack_pallas(w, b), words)
    # b = 32 is the identity layout: no kernel to launch
    assert ("tpu_custom_call" in pack) == (b < 32)
    assert ("tpu_custom_call" in unpack) == (b < 32)


def test_quant_compiles(one_chip):
    x = jax.ShapeDtypeStruct((N,), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compile(quant.quantize_pallas, x)


def test_hybrid_expansion_compiles(one_chip):
    """The ELL/hybrid push and pull (the XLA gather that replaced the Pallas
    SpMV) at 1x1 scale-22 widths, with the bitmap packing kernel inside."""
    k, r_cap = 8, M  # split and residue capacity the scale-22 graph needs
    backend = expand_mod.resolve("hybrid")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def push(nbr, rs, rd, ptr, f):
        blk = backend.local_block(rs, rd, ptr, (nbr, rs, rd, ptr), N, N)
        return backend.push_planes(blk, f)

    def pull(nbr, rs, rd, ptr, f, u):
        blk = backend.local_block(rs, rd, ptr, (nbr, rs, rd, ptr), N, N)
        return backend.pull_planes(blk, f, u)

    args = (sds((N, k), jnp.int32), sds((r_cap,), jnp.int32),
            sds((r_cap,), jnp.int32), sds((N + 1,), jnp.int32),
            sds((B, N), jnp.bool_))
    assert "tpu_custom_call" in _compile(push, *args)
    assert "tpu_custom_call" in _compile(pull, *args, sds((B, N), jnp.bool_))


def test_distributed_bfs_level_compiles(topo):
    """One level of the harness-default driver (auto wire plan,
    direction_opt, hybrid) on a described 2x2 mesh at scale-22 widths."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("data", "model"))
    part = Partition2D(n=N, n_orig=N, rows=2, cols=2)
    e_cap = 2 * M // 4  # 2x the mean block
    k = 8
    cfg = dbfs.DistBFSConfig(mode="auto", policy="direction_opt",
                             expand="hybrid", max_levels=1)

    def blocks(*tail):
        spec = P("data", "model", *(None,) * len(tail))
        return jax.ShapeDtypeStruct((2, 2, *tail), jnp.int32,
                                    sharding=NamedSharding(mesh, spec))

    roots = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=NamedSharding(mesh, P()))
    args = (blocks(e_cap), blocks(e_cap), blocks(part.n_r + 1),
            blocks(part.n_r, k), blocks(e_cap), blocks(e_cap),
            blocks(part.n_r + 1), roots)
    hlo = _compile(dbfs.build_bfs(mesh, part, cfg), *args)
    assert "tpu_custom_call" in hlo
    assert "all-gather" in hlo or "all-reduce" in hlo
