"""Multi-device integration tests (subprocess with forced host devices).

Each test spawns a fresh interpreter with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` because the main
pytest process must keep the default single device (dryrun.py rule).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(snippet: str, devices: int = 4, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", snippet],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


@pytest.mark.slow
def test_distributed_bfs_all_modes_4dev():
    out = _run(
        f"import runpy, sys; sys.argv=['x']; "
        f"runpy.run_path(r'{os.path.join(REPO, 'scripts', 'check_dist_bfs.py')}', "
        f"run_name='__main__')"
    )
    assert "DIST BFS ALL MODES OK" in out


@pytest.mark.slow
def test_distributed_bfs_multipod_fold_8dev():
    out = _run(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.core import csr as csrmod, distributed_bfs as dbfs, validate
from repro.graphgen import builder, kronecker
g = builder.build_csr(kronecker.kronecker_edges(10, seed=3), n=1<<10)
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
bg = csrmod.partition_2d(g, rows=4, cols=2)  # chunk stays a 1024-multiple
cfg = dbfs.DistBFSConfig(row_axes=("pod", "data"), col_axis="model", mode="auto")
fn = dbfs.build_bfs(mesh, bg, cfg)
blocks = dbfs.shard_blocked(mesh, bg, cfg)
parent, level, depth = fn(*blocks, jnp.int32(0))
ref = validate.reference_bfs(g, 0)
assert np.array_equal(np.asarray(level)[:g.n], ref)
res = validate.validate_bfs_tree(g, np.asarray(parent)[:g.n], 0)
assert res.ok, res.failures
print("MULTIPOD OK")
""",
        devices=8,
    )
    assert "MULTIPOD OK" in out


@pytest.mark.slow
def test_gnn_2d_matches_single_device_4dev():
    out = _run(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.models import gnn, gnn_dist
from repro.core import csr as csrmod
from repro.graphgen import builder, kronecker
mesh = jax.make_mesh((2, 2), ("data", "model"))
g = builder.build_csr(kronecker.kronecker_edges(9, seed=5), n=1<<9)
bg = csrmod.partition_2d(g, rows=2, cols=2, chunk_multiple=256)
part = bg.part
rng = np.random.default_rng(0)
n, d_in = part.n, 12
nf = rng.normal(size=(n, d_in)).astype(np.float32)
pos = rng.normal(size=(n, 3)).astype(np.float32)
targets = rng.integers(0, 16, n).astype(np.int32)
for cfg in [gnn.GraphCastConfig(n_layers=2, d_hidden=16, d_in=d_in, d_out=16, edge_state=False),
            gnn.GATConfig(n_layers=2, d_hidden=8, n_heads=2, d_in=d_in, d_out=16),
            gnn.EGNNConfig(n_layers=2, d_hidden=16, d_in=d_in, d_out=16),
            gnn.NequIPConfig(n_layers=2, d_hidden=4, d_in=d_in, d_out=16)]:
    params = gnn.init(cfg, jax.random.PRNGKey(0))
    stepf, _ = gnn_dist.build_2d_train_step(mesh, cfg, part, bg.e_cap)
    r, c, s = part.rows, part.cols, part.chunk
    loss, grads = stepf(params, jnp.asarray(nf.reshape(r,c,s,d_in)), jnp.asarray(pos.reshape(r,c,s,3)),
                        jnp.asarray(bg.src_local), jnp.asarray(bg.dst_local), jnp.asarray(targets.reshape(r,c,s)))
    src_g = np.where(bg.src_local < part.n_c, bg.src_local + (np.arange(c)*part.n_c)[None,:,None], n).reshape(-1)
    dst_g = np.where(bg.dst_local < part.n_r, bg.dst_local + (np.arange(r)*part.n_r)[:,None,None], n).reshape(-1)
    gg = gnn.Graph(nf=jnp.asarray(nf), src=jnp.asarray(src_g, dtype=jnp.int32),
                   dst=jnp.asarray(dst_g, dtype=jnp.int32), pos=jnp.asarray(pos))
    ref = gnn.loss_fn(cfg, params, {"graph": gg, "targets": jnp.asarray(targets)})
    assert abs(float(loss) - float(ref)) < 1e-4, (cfg.name, float(loss), float(ref))
print("GNN2D OK")
""",
        devices=4,
    )
    assert "GNN2D OK" in out


@pytest.mark.slow
def test_gnn_2d_int8_payload_4dev():
    """Quantized halo payloads: loss stays close to fp32 and STE gradients
    flow (the beyond-paper int8 wire format for 2D GNN feature exchange)."""
    out = _run(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.models import gnn, gnn_dist
from repro.core import csr as csrmod
from repro.graphgen import builder, kronecker
mesh = jax.make_mesh((2, 2), ("data", "model"))
g = builder.build_csr(kronecker.kronecker_edges(9, seed=5), n=1<<9)
bg = csrmod.partition_2d(g, rows=2, cols=2, chunk_multiple=256)
part = bg.part
rng = np.random.default_rng(0)
n, d_in = part.n, 12
nf = rng.normal(size=(n, d_in)).astype(np.float32)
pos = rng.normal(size=(n, 3)).astype(np.float32)
targets = rng.integers(0, 16, n).astype(np.int32)
cfg = gnn.GraphCastConfig(n_layers=2, d_hidden=16, d_in=d_in, d_out=16, edge_state=False)
params = gnn.init(cfg, jax.random.PRNGKey(0))
r, c, s = part.rows, part.cols, part.chunk
args = (params, jnp.asarray(nf.reshape(r,c,s,d_in)), jnp.asarray(pos.reshape(r,c,s,3)),
        jnp.asarray(bg.src_local), jnp.asarray(bg.dst_local), jnp.asarray(targets.reshape(r,c,s)))
losses = {}
for q in (False, True):
    dcfg = gnn_dist.Dist2DConfig(quantize_payload=q)
    stepf, _ = gnn_dist.build_2d_train_step(mesh, cfg, part, bg.e_cap, dcfg)
    loss, grads = stepf(*args)
    gn = sum(float(jnp.abs(x).sum()) for x in jax.tree.leaves(grads))
    assert np.isfinite(float(loss)) and np.isfinite(gn) and gn > 0, (q, loss, gn)
    losses[q] = float(loss)
rel = abs(losses[True] - losses[False]) / abs(losses[False])
assert rel < 0.05, losses  # int8 wire format changes the loss <5%
print("INT8 PAYLOAD OK", losses)
""",
        devices=4,
    )
    assert "INT8 PAYLOAD OK" in out


@pytest.mark.slow
def test_dp_train_int8_ef_4dev():
    out = _run(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.optim import adamw
from repro.train import step as tstep
mesh = jax.make_mesh((4,), ("data",))
def loss_fn(params, batch):
    return jnp.mean((batch["x"] @ params["w"] - batch["y"])**2)
rng = np.random.default_rng(0)
w_true = rng.normal(size=(16,)).astype(np.float32)
state = tstep.init_state({"w": jnp.zeros(16)}, with_ef=True)
ocfg = adamw.AdamWConfig(lr=0.05, weight_decay=0.0, warmup_steps=0, total_steps=10_000)
stepf = tstep.make_dp_train_step(loss_fn, ocfg, mesh, compress=True)
for i in range(150):
    x = rng.normal(size=(64, 16)).astype(np.float32)
    state, m = stepf(state, {"x": jnp.asarray(x), "y": jnp.asarray(x @ w_true)})
assert float(m["loss"]) < 1e-2, float(m["loss"])
print("DP-EF OK", float(m["loss"]))
""",
        devices=4,
    )
    assert "DP-EF OK" in out


@pytest.mark.slow
def test_sparse_packed_branches_execute_4dev():
    """At realistic chunk sizes (s=65536) the ladder has sparse buckets and
    the packed branch of the switch actually executes — correct for every
    density regime (packed buckets AND bitmap fallback)."""
    out = _run(
        """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import comm as cc
mesh = jax.make_mesh((4,), ("data",))
s = 65536
ladder = cc.BucketLadder.default(s)
assert ladder.specs, "ladder must have sparse buckets at s=65536"
def gathered(bits):
    f = jax.shard_map(lambda b: cc.allgather_membership(b.reshape(-1), ("data",), ladder, 4),
                  mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False)
    return jax.jit(f)(bits)
rng = np.random.default_rng(0)
for count_per_rank in (50, 900, 8000, 40000):
    bits_np = np.zeros(4 * s, bool)
    for r in range(4):
        idx = rng.choice(s, count_per_rank, replace=False)
        bits_np[r * s + idx] = True
    out = np.asarray(gathered(jnp.asarray(bits_np))).reshape(4, 4 * s)
    assert all(np.array_equal(row, bits_np) for row in out), count_per_rank
print("SPARSE BRANCHES OK")
""",
        devices=4,
    )
    assert "SPARSE BRANCHES OK" in out


@pytest.mark.slow
def test_bfs_scale18_all_buckets_4dev():
    """End-to-end distributed BFS at scale 18 (s=65536): sparse id-stream
    buckets live in BOTH phases (col [1024]; row [1024,4096,16384]) and the
    result still matches the oracle + Graph500 rules."""
    out = _run(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.core import csr as csrmod, distributed_bfs as dbfs, validate
from repro import comm as cc
from repro.graphgen import builder, kronecker
g = builder.build_csr(kronecker.kronecker_edges(18, seed=3), n=1<<18)
mesh = jax.make_mesh((2, 2), ("data", "model"))
bg = csrmod.partition_2d(g, rows=2, cols=2)
assert cc.BucketLadder.default(bg.part.chunk).specs  # sparse buckets exist
fn = dbfs.build_bfs(mesh, bg, dbfs.DistBFSConfig(mode="auto"))
blocks = dbfs.shard_blocked(mesh, bg, dbfs.DistBFSConfig(mode="auto"))
root = int(np.argmax(g.degrees()))
parent, level, depth = fn(*blocks, jnp.int32(root))
assert np.array_equal(np.asarray(level)[:g.n], validate.reference_bfs(g, root))
assert validate.validate_bfs_tree(g, np.asarray(parent)[:g.n], root).ok
print("SCALE18 OK")
""",
        devices=4,
        timeout=1200,
    )
    assert "SCALE18 OK" in out


@pytest.mark.slow
def test_compressed_allgather_membership_4dev():
    """The bucketed compressed all-gather reproduces plain all-gather for
    sparse AND dense memberships (both switch branches exercised)."""
    out = _run(
        """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import comm as cc
mesh = jax.make_mesh((4,), ("data",))
s = 2048
ladder = cc.BucketLadder.default(s)
def gathered(bits):
    f = jax.shard_map(lambda b: cc.allgather_membership(b.reshape(-1), ("data",), ladder, 4),
                  mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False)
    return jax.jit(f)(bits)
rng = np.random.default_rng(0)
for density in (0.001, 0.02, 0.5):
    bits = jnp.asarray(rng.random(4 * s) < density)
    out = np.asarray(gathered(bits))
    # every device returns the full gathered membership; out_specs P('data')
    # concatenates the 4 identical copies
    got = out.reshape(4, 4 * s)
    ref = np.asarray(bits)
    assert all(np.array_equal(row, ref) for row in got), density
print("CC-ALLGATHER OK")
""",
        devices=4,
    )
    assert "CC-ALLGATHER OK" in out
