"""Cell builders: (arch x shape x mesh) -> lowerable function + specs + meta.

A *cell* is one entry of the 40-cell dry-run grid (plus the paper's own
graph500 cells).  ``build_cell`` returns everything ``dryrun.py`` needs:

* ``fn``            — the jit-able step (train_step / prefill / decode /
                      serve / retrieval / bfs),
* ``args``          — ShapeDtypeStruct pytree (no allocation, ever),
* ``in_shardings``  — NamedSharding pytree for the production mesh,
* ``meta``          — analytic MODEL_FLOPS, param counts, loop multiplier
                      for the roofline HLO scaling (scan bodies count once).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import common as cfgs
from repro.core import distributed_bfs as dbfs
from repro.core.csr import Partition2D
from repro.launch import mesh as meshlib
from repro.models import gnn, gnn_dist, recsys
from repro.models import transformer as tfm
from repro.optim import adamw
from repro.train import step as tstep


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_name: str
    kind: str
    fn: Callable | None = None
    args: tuple = ()
    in_shardings: Any = None
    meta: dict = dataclasses.field(default_factory=dict)
    skip_reason: str = ""

    @property
    def cell_id(self) -> str:
        return f"{self.arch_id}/{self.shape_name}"


def _shard(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda sp: None if sp is None else NamedSharding(mesh, sp),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P) or x is None,
    )


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# analytic FLOPs (MODEL_FLOPS for the roofline: useful work, global)
# ---------------------------------------------------------------------------


def lm_train_flops(cfg: tfm.TransformerConfig, batch: int, seq: int) -> float:
    tokens = batch * seq
    dense = 6.0 * cfg.n_active_params() * tokens
    attn_fwd = batch * cfg.n_layers * cfg.n_heads * seq * seq * (
        cfg.qk_head_dim + (cfg.v_head_dim if cfg.use_mla else cfg.head_dim)
    )
    return dense + 3.0 * attn_fwd


def lm_prefill_flops(cfg: tfm.TransformerConfig, batch: int, seq: int) -> float:
    tokens = batch * seq
    dense = 2.0 * cfg.n_active_params() * tokens
    attn = batch * cfg.n_layers * cfg.n_heads * seq * seq * (
        cfg.qk_head_dim + (cfg.v_head_dim if cfg.use_mla else cfg.head_dim)
    )
    return dense + attn


def lm_decode_flops(cfg: tfm.TransformerConfig, batch: int, seq: int) -> float:
    dense = 2.0 * cfg.n_active_params() * batch
    if cfg.use_mla:  # absorbed decode reads the latent cache
        attn = 2.0 * batch * cfg.n_layers * cfg.n_heads * seq * (
            cfg.kv_lora_rank + cfg.qk_rope_dim
        ) * 2
    else:
        attn = 2.0 * batch * cfg.n_layers * cfg.n_heads * seq * 2 * cfg.head_dim
    return dense + attn


def _mlp_flops(dims: tuple[int, ...]) -> float:
    return 2.0 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def gnn_flops(cfg, n: int, m: int, d_in: int) -> float:
    if isinstance(cfg, gnn.GraphCastConfig):
        d = cfg.d_hidden
        per_layer = m * _mlp_flops((3 * d, d, d)) + n * _mlp_flops((2 * d, d, d))
        return cfg.n_layers * per_layer + n * (
            _mlp_flops((d_in, d, d)) + _mlp_flops((d, d, cfg.d_out))
        )
    if isinstance(cfg, gnn.GATConfig):
        f = 0.0
        d_prev = d_in
        for i in range(cfg.n_layers):
            last = i == cfg.n_layers - 1
            heads = 1 if last else cfg.n_heads
            d_o = cfg.d_out if last else cfg.d_hidden
            f += 2.0 * n * heads * d_prev * d_o + 6.0 * m * heads * d_o
            d_prev = heads * d_o
        return f
    if isinstance(cfg, gnn.EGNNConfig):
        d = cfg.d_hidden
        per_layer = m * (_mlp_flops((2 * d + 1, d, d)) + _mlp_flops((d, d, 1))) + n * _mlp_flops(
            (2 * d, d, d)
        )
        return cfg.n_layers * per_layer + n * (
            _mlp_flops((cfg.d_in, d)) + _mlp_flops((d, cfg.d_out))
        )
    if isinstance(cfg, gnn.NequIPConfig):
        c = cfg.d_hidden
        # radial MLP + tensor-product paths (13c floats/node state)
        per_edge = _mlp_flops((cfg.n_rbf, c, 3 * c)) + 2.0 * 13 * c * 9
        per_node = 2.0 * 3 * c * c + _mlp_flops((c, 2 * c))
        return cfg.n_layers * (m * per_edge + n * per_node)
    raise TypeError(type(cfg))


def recsys_flops(cfg: recsys.AutoIntConfig, batch: int) -> float:
    f, d, da, h = cfg.n_sparse, cfg.embed_dim, cfg.d_attn, cfg.n_heads
    flops = 0.0
    d_prev = d
    for _ in range(cfg.n_attn_layers):
        flops += batch * (
            3 * 2 * f * h * d_prev * da + 2 * 2 * h * f * f * da + 2 * f * d_prev * h * da
        )
        d_prev = h * da
    dims = (f * d_prev,) + cfg.mlp_dims + (1,)
    flops += batch * _mlp_flops(dims)
    return flops


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------


def _lm_cell(
    spec: cfgs.ArchSpec, shape: cfgs.ShapeSpec, mesh: Mesh, variant: str = "baseline"
) -> Cell:
    cfg: tfm.TransformerConfig = spec.model_config()
    fsdp = meshlib.fsdp_axes(mesh)
    chips = mesh.size
    # --- §Perf variants (EXPERIMENTS.md) -----------------------------------
    if "bf16" in variant:  # bf16 param storage (fp32 Adam moments kept)
        cfg = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)
    if "moegroup256" in variant and cfg.is_moe:
        cfg = dataclasses.replace(cfg, moe_group=256)
    if "noremat" in variant:
        cfg = dataclasses.replace(cfg, remat=False)
    if "dotsave" in variant:  # the ORIGINAL (pathological) remat policy
        cfg = dataclasses.replace(cfg, remat_policy="dots")
    if "moepin" in variant and cfg.is_moe:  # pin MoE dispatch shardings
        cfg = dataclasses.replace(cfg, moe_dp_axes=fsdp, moe_tp_axis="model")
    if "experttp" in variant and cfg.is_moe:  # resident expert weights
        cfg = dataclasses.replace(cfg, expert_shard="ff")
    serve_fsdp = () if "tpserve" in variant else fsdp  # TP-only serving params
    # -----------------------------------------------------------------------
    p_specs = tfm.param_specs(cfg, fsdp=fsdp, tp="model")
    batch = shape.params["global_batch"]
    seq = shape.params["seq_len"]
    dp = fsdp if len(fsdp) > 1 else fsdp[0]

    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig()
        step_fn = tstep.make_train_step(functools.partial(tfm.loss_fn, cfg), opt_cfg)
        state = jax.eval_shape(
            lambda: tstep.init_state(tfm.init_params(cfg, jax.random.PRNGKey(0)))
        )
        state_specs = tstep.TrainState(
            params=p_specs,
            opt=adamw.OptState(step=P(), m=p_specs, v=p_specs),
            ef=None,
        )
        batch_sds = {"tokens": _sds((batch, seq), jnp.int32)}
        batch_specs = {"tokens": P(dp, None)}
        return Cell(
            spec.arch_id, shape.name, "train",
            fn=step_fn,
            args=(state, batch_sds),
            in_shardings=(_shard(mesh, state_specs), _shard(mesh, batch_specs)),
            meta=dict(
                model_flops=lm_train_flops(cfg, batch, seq),
                n_params=cfg.n_params(),
                n_active=cfg.n_active_params(),
                loop_mult=float(cfg.n_layers),
            ),
        )

    params = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    if shape.kind in ("prefill", "decode") and not serve_fsdp:
        # serving layout: weights fully TP-sharded + replicated over data —
        # no per-step FSDP weight all-gather on the latency path
        p_specs = jax.tree.map(
            lambda sp: P(*[("model" if e == "model" else None) for e in sp]),
            p_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
    if shape.kind == "prefill":
        fn = functools.partial(tfm.prefill, cfg)
        toks = _sds((batch, seq), jnp.int32)
        return Cell(
            spec.arch_id, shape.name, "prefill",
            fn=fn,
            args=(params, toks),
            in_shardings=(_shard(mesh, p_specs), NamedSharding(mesh, P(dp, None))),
            meta=dict(
                model_flops=lm_prefill_flops(cfg, batch, seq),
                n_params=cfg.n_params(),
                loop_mult=float(cfg.n_layers),
            ),
        )

    if shape.kind == "decode":
        fn = functools.partial(tfm.decode_step, cfg)
        cache = _sds((cfg.n_layers, batch, seq, cfg.cache_width), cfg.compute_dtype)
        toks = _sds((batch,), jnp.int32)
        pos = _sds((batch,), jnp.int32)
        cache_sh = NamedSharding(mesh, tfm.cache_spec(fsdp=fsdp, tp="model"))
        return Cell(
            spec.arch_id, shape.name, "decode",
            fn=fn,
            args=(params, cache, toks, pos),
            in_shardings=(
                _shard(mesh, p_specs),
                cache_sh,
                NamedSharding(mesh, P(dp)),
                NamedSharding(mesh, P(dp)),
            ),
            meta=dict(
                model_flops=lm_decode_flops(cfg, batch, seq),
                n_params=cfg.n_params(),
                cache_bytes=cfg.n_layers * batch * seq * cfg.cache_width
                * np.dtype(cfg.compute_dtype).itemsize,
                loop_mult=float(cfg.n_layers),
            ),
        )
    raise ValueError(shape.kind)


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------


def _gnn_model_cfg(spec: cfgs.ArchSpec, d_in: int, d_out: int):
    return spec.model_config(d_in=d_in, d_out=d_out)


def _gnn_cell(spec: cfgs.ArchSpec, shape: cfgs.ShapeSpec, mesh: Mesh) -> Cell:
    p = shape.params
    dist = p["dist"]
    fsdp = meshlib.fsdp_axes(mesh)
    dp = fsdp if len(fsdp) > 1 else fsdp[0]

    if dist == "2d":
        return _gnn_2d_cell(spec, shape, mesh)

    if dist == "batched":
        n = p["n_nodes"] * p["batch"]
        m = p["n_edges"] * p["batch"]
    elif dist == "sampled":
        from repro.data.graphs import sampled_shape

        n, m = sampled_shape(p["batch_nodes"], p["fanout"])
    else:
        n, m = p["n_nodes"], p["n_edges"]
    d_in, n_classes = p["d_feat"], p["n_classes"]
    cfg = _gnn_model_cfg(spec, d_in, n_classes)
    if isinstance(cfg, gnn.GraphCastConfig):
        cfg = dataclasses.replace(cfg, edge_state=dist not in ("2d",))

    params = jax.eval_shape(lambda: gnn.init(cfg, jax.random.PRNGKey(0)))
    opt_cfg = adamw.AdamWConfig()
    loss = functools.partial(gnn.loss_fn, cfg)
    step_fn = tstep.make_train_step(loss, opt_cfg)
    state = jax.eval_shape(lambda: tstep.init_state(gnn.init(cfg, jax.random.PRNGKey(0))))
    rep = jax.tree.map(lambda _: P(), params)
    state_specs = tstep.TrainState(
        params=rep, opt=adamw.OptState(step=P(), m=rep, v=rep), ef=None
    )
    # nodes/edges sharded over the data axes when divisible, else replicated
    dp_prod = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_prod *= mesh.shape[a]
    node_ax = dp if n % dp_prod == 0 else None
    edge_ax = dp if m % dp_prod == 0 else None

    graph_sds = gnn.Graph(
        nf=_sds((n, d_in), jnp.float32),
        src=_sds((m,), jnp.int32),
        dst=_sds((m,), jnp.int32),
        pos=_sds((n, 3), jnp.float32),
    )
    graph_specs = gnn.Graph(
        nf=P(node_ax, None), src=P(edge_ax), dst=P(edge_ax), pos=P(node_ax, None)
    )
    batch_sds = {"graph": graph_sds, "targets": _sds((n,), jnp.int32)}
    batch_specs = {"graph": graph_specs, "targets": P(node_ax)}
    return Cell(
        spec.arch_id, shape.name, "graph_train",
        fn=step_fn,
        args=(state, batch_sds),
        in_shardings=(_shard(mesh, state_specs), _shard(mesh, batch_specs)),
        meta=dict(
            model_flops=3.0 * gnn_flops(cfg, n, m, d_in),
            n_params=sum(x.size for x in jax.tree.leaves(params)),
            loop_mult=1.0,
            n_nodes=n,
            n_edges=m,
        ),
    )


def _gnn_2d_cell(spec: cfgs.ArchSpec, shape: cfgs.ShapeSpec, mesh: Mesh) -> Cell:
    p = shape.params
    rows, cols = meshlib.grid_rows_cols(mesh)
    n_pad = _round_up(p["n_nodes"], rows * cols * 1024)
    part = Partition2D(n=n_pad, n_orig=p["n_nodes"], rows=rows, cols=cols)
    e_cap = _round_up(2 * p["n_edges"] // (rows * cols), 1024)
    d_in, n_classes = p["d_feat"], p["n_classes"]
    cfg = _gnn_model_cfg(spec, d_in, n_classes)
    if isinstance(cfg, gnn.GraphCastConfig):
        cfg = dataclasses.replace(cfg, edge_state=False)
    dcfg = gnn_dist.Dist2DConfig(
        row_axes=meshlib.fsdp_axes(mesh),
        col_axis="model",
        quantize_payload=spec.arch_id in ("graphcast", "gat-cora"),
    )
    step_fn, in_specs = gnn_dist.build_2d_train_step(mesh, cfg, part, e_cap, dcfg)
    params = jax.eval_shape(lambda: gnn.init(cfg, jax.random.PRNGKey(0)))
    s = part.chunk
    ax_sizes = tuple(mesh.shape[a] for a in dcfg.all_axes)
    args = (
        params,
        _sds(ax_sizes + (s, d_in), jnp.float32),
        _sds(ax_sizes + (s, 3), jnp.float32),
        _sds(ax_sizes + (e_cap,), jnp.int32),
        _sds(ax_sizes + (e_cap,), jnp.int32),
        _sds(ax_sizes + (s,), jnp.int32),
    )
    # params replicated; data arrays owner-chunk / block sharded
    in_sh = (_shard(mesh, jax.tree.map(lambda _: P(), params)),) + tuple(
        NamedSharding(mesh, sp) for sp in in_specs[1:]
    )
    return Cell(
        spec.arch_id, shape.name, "graph_train_2d",
        fn=step_fn,
        args=args,
        in_shardings=in_sh,
        meta=dict(
            model_flops=3.0 * gnn_flops(cfg, p["n_nodes"], p["n_edges"], d_in),
            n_params=sum(x.size for x in jax.tree.leaves(params)),
            loop_mult=1.0,
            n_nodes=p["n_nodes"],
            n_edges=p["n_edges"],
            e_cap=e_cap,
        ),
    )


# ---------------------------------------------------------------------------
# recsys cells
# ---------------------------------------------------------------------------


def _recsys_cell(
    spec: cfgs.ArchSpec, shape: cfgs.ShapeSpec, mesh: Mesh, variant: str = "baseline"
) -> Cell:
    cfg: recsys.AutoIntConfig = spec.model_config()
    fsdp = meshlib.fsdp_axes(mesh)
    all_axes = fsdp + ("model",)
    p_specs = recsys.param_specs(cfg, fsdp=fsdp, tp="model")
    if "int8table" in variant:
        cfg = dataclasses.replace(cfg, table_quant=True)
        p_specs = recsys.param_specs(cfg, fsdp=fsdp, tp="model")
        p_specs = dict(p_specs, table_scale=P(fsdp + ("model",)))
    if "modeltable" in variant:
        # §Perf: shard table rows over 'model' ONLY (replicated across data
        # axes) — lookups stay inside 16-way groups instead of 512-way
        p_specs = dict(p_specs, table=P("model", None))
        if "int8table" in variant:
            p_specs = dict(p_specs, table_scale=P("model"))
    params = jax.eval_shape(lambda: recsys.init_params(cfg, jax.random.PRNGKey(0)))
    f = cfg.n_sparse

    if shape.kind == "train":
        b = shape.params["batch"]
        opt_cfg = adamw.AdamWConfig()
        step_fn = tstep.make_train_step(functools.partial(recsys.loss_fn, cfg), opt_cfg)
        state = jax.eval_shape(
            lambda: tstep.init_state(recsys.init_params(cfg, jax.random.PRNGKey(0)))
        )
        state_specs = tstep.TrainState(
            params=p_specs, opt=adamw.OptState(step=P(), m=p_specs, v=p_specs), ef=None
        )
        batch_sds = {"ids": _sds((b, f), jnp.int32), "labels": _sds((b,), jnp.float32)}
        batch_specs = {"ids": P(all_axes, None), "labels": P(all_axes)}
        return Cell(
            spec.arch_id, shape.name, "train",
            fn=step_fn,
            args=(state, batch_sds),
            in_shardings=(_shard(mesh, state_specs), _shard(mesh, batch_specs)),
            meta=dict(
                model_flops=3.0 * recsys_flops(cfg, b),
                n_params=cfg.n_params(),
                lookup_bytes=b * f * cfg.embed_dim * 4,
                loop_mult=1.0,
            ),
        )

    if shape.kind == "serve":
        b = shape.params["batch"]
        fn = functools.partial(recsys.forward, cfg)
        ids = _sds((b, f), jnp.int32)
        return Cell(
            spec.arch_id, shape.name, "serve",
            fn=fn,
            args=(params, ids),
            in_shardings=(_shard(mesh, p_specs), NamedSharding(mesh, P(all_axes, None))),
            meta=dict(
                model_flops=recsys_flops(cfg, b),
                n_params=cfg.n_params(),
                lookup_bytes=b * f * cfg.embed_dim * 4,
                loop_mult=1.0,
            ),
        )

    if shape.kind == "retrieval":
        nc = shape.params["n_candidates"]
        nc_pad = _round_up(nc, mesh.size)
        fn = functools.partial(recsys.retrieval_scores, cfg)
        ids = _sds((1, f), jnp.int32)
        cand = _sds((nc_pad,), jnp.int32)
        return Cell(
            spec.arch_id, shape.name, "retrieval",
            fn=fn,
            args=(params, ids, cand),
            in_shardings=(
                _shard(mesh, p_specs),
                NamedSharding(mesh, P(None, None)),
                NamedSharding(mesh, P(all_axes)),
            ),
            meta=dict(
                model_flops=recsys_flops(cfg, 1) + 2.0 * nc * cfg.embed_dim,
                n_params=cfg.n_params(),
                lookup_bytes=nc * cfg.embed_dim * 4,
                loop_mult=1.0,
            ),
        )
    raise ValueError(shape.kind)


# ---------------------------------------------------------------------------
# graph500 (the paper's workload)
# ---------------------------------------------------------------------------


def _graph500_cell(
    spec: cfgs.ArchSpec, shape: cfgs.ShapeSpec, mesh: Mesh, variant: str = "baseline"
) -> Cell:
    from repro.configs.graph500 import Graph500Config

    cfg: Graph500Config = spec.model_config()
    scale, ef = shape.params["scale"], shape.params["edgefactor"]
    rows, cols = meshlib.grid_rows_cols(mesh)
    n = _round_up(1 << scale, rows * cols * 1024)
    part = Partition2D(n=n, n_orig=1 << scale, rows=rows, cols=cols)
    m_sym = 2 * ef * (1 << scale)
    # baseline: 4x mean block capacity (pessimistic RMAT-skew headroom);
    # §Perf variant 'ecap15': 1.5x, justified by measured block imbalance of
    # label-permuted RMAT graphs (benchmarks/frontier_stats + EXPERIMENTS.md)
    skew = 1.5 if "ecap15" in variant else 4.0
    e_cap = _round_up(int(skew * m_sym) // (rows * cols), 1024)
    row_axes = meshlib.fsdp_axes(mesh)
    mode = "bitmap" if "bitmaponly" in variant else cfg.mode
    bcfg = dbfs.DistBFSConfig(row_axes=row_axes, col_axis="model", mode=mode)
    fn = dbfs.build_bfs(mesh, part, bcfg)
    ax_sizes = tuple(mesh.shape[a] for a in bcfg.all_axes)
    blk = _sds(ax_sizes + (e_cap,), jnp.int32)
    ptr = _sds(ax_sizes + (part.n_r + 1,), jnp.int32)  # the blocks' row offsets
    blk_sh = NamedSharding(mesh, P(*bcfg.row_axes, bcfg.col_axis, None))
    return Cell(
        spec.arch_id, shape.name, "bfs",
        fn=fn,
        args=(blk, blk, ptr, _sds((), jnp.int32)),
        in_shardings=(blk_sh, blk_sh, blk_sh, NamedSharding(mesh, P())),
        meta=dict(
            model_flops=2.0 * m_sym,  # one compare+select per directed edge
            n_edges=m_sym,
            e_cap=e_cap,
            loop_mult=8.0,  # typical RMAT BFS depth
        ),
    )


# ---------------------------------------------------------------------------


def build_cell(
    arch_id: str, shape_name: str, mesh: Mesh, variant: str = "baseline"
) -> Cell:
    spec = cfgs.get(arch_id)
    shape = spec.shape(shape_name)
    if shape.kind == "skip":
        return Cell(arch_id, shape_name, "skip", skip_reason=shape.skip_reason)
    if spec.family == "lm":
        return _lm_cell(spec, shape, mesh, variant)
    if spec.family == "gnn":
        return _gnn_cell(spec, shape, mesh)
    if spec.family == "recsys":
        return _recsys_cell(spec, shape, mesh, variant)
    if spec.family == "graph":
        return _graph500_cell(spec, shape, mesh, variant)
    raise ValueError(spec.family)


def all_cells() -> list[tuple[str, str]]:
    out = []
    for arch in cfgs.list_archs():
        for shape in cfgs.get(arch).shapes:
            out.append((arch, shape.name))
    return out
