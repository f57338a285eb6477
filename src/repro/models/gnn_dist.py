"""2D-partitioned distributed GNN message passing (the paper's SpMV pattern).

Full-batch training on ogbn-products-scale graphs (62M edges) cannot
replicate node features; the scalable layout is exactly the paper's 2D
adjacency partition (DESIGN.md §5 "2D-partitioned message passing IS the
paper's SpMV"):

* node state lives in owned chunks (rank (i,j) owns chunk q = i*C + j,
  width s) — identical geometry to core/distributed_bfs.py;
* per layer, rank (i,j) assembles the **column slice** of source features
  (TransposeVector + all-gather over rows) and the **row slice** of
  destination features (all-gather over columns), computes messages for its
  edge block, segment-reduces into row-slice partials, and an all-to-all
  over columns lands reduced aggregates at owners;
* optional **int8 payload compression** of every feature exchange
  (beyond-paper application of the paper's insight to float payloads;
  straight-through gradients, disabled for equivariance-sensitive archs).

Aggregations support sum and max so attention aggregators (GAT) run as two
passes: a max pass (softmax stability) then a fused exp-sum pass.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.csr import Partition2D
from repro.kernels.quant import ref as quant

NEG = -1e30


@dataclasses.dataclass(frozen=True)
class Dist2DConfig:
    row_axes: tuple[str, ...] = ("data",)
    col_axis: str = "model"
    quantize_payload: bool = False  # int8 wire format for feature exchanges

    @property
    def all_axes(self) -> tuple[str, ...]:
        return self.row_axes + (self.col_axis,)


@jax.custom_vjp
def _ste_quant(x):
    """Quantize-dequantize with straight-through gradient."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % quant.GROUP
    flat = jnp.pad(flat, (0, pad))
    q, s = quant.quantize(flat)
    out = quant.dequantize(q, s)
    return out[: x.size].reshape(x.shape).astype(x.dtype)


def _ste_fwd(x):
    return _ste_quant(x), None


def _ste_bwd(_, g):
    return (g,)


_ste_quant.defvjp(_ste_fwd, _ste_bwd)


def _wire(x, cfg: Dist2DConfig):
    return _ste_quant(x) if cfg.quantize_payload else x


def gather_col_row(h_own, part: Partition2D, cfg: Dist2DConfig):
    """Owned chunk (s, d) -> (column slice (n_c, d), row slice (n_r, d))."""
    perm = part.transpose_perm()
    h_t = jax.lax.ppermute(_wire(h_own, cfg), cfg.all_axes, perm)
    h_col = jax.lax.all_gather(h_t, cfg.row_axes, tiled=True)
    h_row = jax.lax.all_gather(_wire(h_own, cfg), cfg.col_axis, tiled=True)
    return h_col, h_row


def reduce_to_owned(partial, part: Partition2D, cfg: Dist2DConfig, op: str = "sum"):
    """Row-slice partials (n_r, d) -> owned aggregates (s, d) via all-to-all."""
    c, s = part.cols, part.chunk
    chunks = partial.reshape(c, s, -1)
    recv = jax.lax.all_to_all(_wire(chunks, cfg), cfg.col_axis, 0, 0, tiled=True)
    recv = recv.reshape(c, s, -1)
    return jnp.max(recv, axis=0) if op == "max" else jnp.sum(recv, axis=0)


def _gather_feat(h, idx, n):
    hz = jnp.concatenate([h, jnp.zeros_like(h[:1])], axis=0)
    return hz[jnp.minimum(idx, n)]


def aggregate_2d(
    h_own,
    edge_fn: Callable[[jax.Array, jax.Array], jax.Array],
    src_l,
    dst_l,
    part: Partition2D,
    cfg: Dist2DConfig,
    op: str = "sum",
    h_aux_own=None,
):
    """One 2D aggregation pass.

    ``edge_fn(h_src (m, d), h_dst (m, d)) -> messages (m, dm)``; padding
    edges (src_l == n_c) produce identity elements.  Returns owned (s, dm).
    """
    n_r, n_c, s = part.n_r, part.n_c, part.chunk
    payload = h_own if h_aux_own is None else jnp.concatenate([h_own, h_aux_own], -1)
    p_col, p_row = gather_col_row(payload, part, cfg)
    hs = _gather_feat(p_col, src_l, n_c)
    hd = _gather_feat(p_row, dst_l, n_r)
    msg = edge_fn(hs, hd)
    valid = (src_l < n_c)[:, None]
    ident = jnp.float32(0.0) if op == "sum" else jnp.float32(NEG)
    msg = jnp.where(valid, msg, ident).astype(msg.dtype)
    seg_op = jax.ops.segment_sum if op == "sum" else jax.ops.segment_max
    partial = seg_op(msg, dst_l, num_segments=n_r + 1)[:n_r]
    if op == "max":
        partial = jnp.maximum(partial, NEG)  # segment_max identity fix
    return reduce_to_owned(partial, part, cfg, op=op)


# ---------------------------------------------------------------------------
# per-arch 2D layers (forward);  params reuse the single-device inits
# ---------------------------------------------------------------------------


def _mlp(params, x):
    for i, lyr in enumerate(params):
        x = x @ lyr["w"] + lyr["b"]
        if i < len(params) - 1:
            x = jax.nn.silu(x)
    return x


def graphcast_2d(cfg_m, params, h_own, src_l, dst_l, part, dcfg):
    """Interaction-network stack, sum aggregation (edge state omitted in the
    distributed variant: messages recomputed per layer — remat-style)."""
    h = _mlp(params["encoder"], h_own)
    d = h.shape[-1]
    for lyr in params["layers"]:
        def edge_fn(hs, hd, lyr=lyr):
            e = _mlp(lyr["edge"], jnp.concatenate([jnp.zeros_like(hs), hs, hd], -1))
            return e

        agg = aggregate_2d(h, edge_fn, src_l, dst_l, part, dcfg, op="sum")
        h = h + _mlp(lyr["node"], jnp.concatenate([h, agg], -1))
    return _mlp(params["decoder"], h)


def gat_2d(cfg_m, params, h_own, src_l, dst_l, part, dcfg):
    """GAT: max pass (stability) then fused exp-sum pass per layer."""
    h = h_own
    for li, lyr in enumerate(params["layers"]):
        heads, d_out = lyr["w"].shape[0], lyr["w"].shape[2]
        z = jnp.einsum("nd,hdo->nho", h, lyr["w"]).reshape(h.shape[0], -1)

        def logits_fn(zs, zd, lyr=lyr, heads=heads, d_out=d_out):
            zs = zs.reshape(-1, heads, d_out)
            zd = zd.reshape(-1, heads, d_out)
            lg = jnp.einsum("mho,ho->mh", zs, lyr["a_src"]) + jnp.einsum(
                "mho,ho->mh", zd, lyr["a_dst"]
            )
            return jax.nn.leaky_relu(lg, 0.2)

        mx = aggregate_2d(z, logits_fn, src_l, dst_l, part, dcfg, op="max")

        def expsum_fn(payload_s, payload_d, lyr=lyr, heads=heads, d_out=d_out):
            zs = payload_s[:, : heads * d_out].reshape(-1, heads, d_out)
            zd = payload_d[:, : heads * d_out].reshape(-1, heads, d_out)
            mxd = payload_d[:, heads * d_out : heads * d_out + heads]
            lg = jnp.einsum("mho,ho->mh", zs, lyr["a_src"]) + jnp.einsum(
                "mho,ho->mh", zd, lyr["a_dst"]
            )
            e = jnp.exp(jax.nn.leaky_relu(lg, 0.2) - mxd)  # (m, h)
            num = (e[..., None] * zs).reshape(e.shape[0], -1)
            return jnp.concatenate([num, e], -1)

        agg = aggregate_2d(
            z, expsum_fn, src_l, dst_l, part, dcfg, op="sum", h_aux_own=mx
        )
        num = agg[:, : heads * d_out].reshape(-1, heads, d_out)
        den = agg[:, heads * d_out :][:, :, None]
        h = (num / jnp.maximum(den, 1e-16)).reshape(h.shape[0], -1)
        if li < len(params["layers"]) - 1:
            h = jax.nn.elu(h)
    return h


def egnn_2d(cfg_m, params, h_own, pos_own, src_l, dst_l, part, dcfg):
    """EGNN: payload = [h, x]; messages and coordinate deltas in one pass."""
    h = _mlp(params["embed"], h_own)
    x = pos_own
    d = h.shape[-1]
    for lyr in params["layers"]:
        def edge_fn(ps, pd, lyr=lyr, d=d):
            hs, xs = ps[:, :d], ps[:, d : d + 3]
            hd, xd = pd[:, :d], pd[:, d : d + 3]
            diff = xd - xs
            d2 = jnp.sum(diff * diff, -1, keepdims=True)
            m = _mlp(lyr["edge"], jnp.concatenate([hs, hd, d2], -1))
            w = jnp.tanh(_mlp(lyr["coord"], m))
            return jnp.concatenate([m, -diff * w, jnp.ones_like(d2)], -1)

        agg = aggregate_2d(
            h, edge_fn, src_l, dst_l, part, dcfg, op="sum", h_aux_own=x
        )
        m_agg, dx, deg = agg[:, :d], agg[:, d : d + 3], agg[:, d + 3 :]
        x = x + dx / jnp.maximum(deg, 1.0)
        h = h + _mlp(lyr["node"], jnp.concatenate([h, m_agg], -1))
    return _mlp(params["out"], h)


def nequip_2d(cfg_m, params, h_own, pos_own, src_l, dst_l, part, dcfg):
    """NequIP: flatten l<=2 irreps into the payload (13c floats/node).

    Payload quantization is force-disabled — lossy wire formats break exact
    equivariance (DESIGN.md §Arch-applicability)."""
    from repro.models import irreps as ir

    dcfg = dataclasses.replace(dcfg, quantize_payload=False)
    c = cfg_m.d_hidden
    n = h_own.shape[0]
    s_f = _mlp(params["embed"], h_own)
    v_f = jnp.zeros((n, c, 3))
    t_f = jnp.zeros((n, c, 3, 3))
    for lyr in params["layers"]:
        payload = jnp.concatenate(
            [s_f, v_f.reshape(n, -1), t_f.reshape(n, -1), pos_own], -1
        )

        def edge_fn(ps, pd, lyr=lyr, c=c):
            m = ps.shape[0]
            hs_s = ps[:, :c]
            hs_v = ps[:, c : 4 * c].reshape(m, c, 3)
            hs_t = ps[:, 4 * c : 13 * c].reshape(m, c, 3, 3)
            xs = ps[:, 13 * c :]
            xd = pd[:, 13 * c :]
            disp = xd - xs
            r = jnp.sqrt(jnp.sum(disp * disp, -1) + 1e-12)
            rhat = disp / r[:, None]
            y1, y2 = ir.sph_l1(rhat), ir.sph_l2(rhat)
            rbf = ir.bessel_rbf(r, cfg_m.n_rbf, cfg_m.cutoff)
            w = _mlp(lyr["radial"], rbf)
            w0, w1, w2 = w[:, :c], w[:, c : 2 * c], w[:, 2 * c :]
            m_s = w0 * (hs_s + ir.p_vv_s(hs_v, y1[:, None, :]))
            m_v = w1[..., None] * (
                hs_s[..., None] * y1[:, None, :] + hs_v + ir.p_tv_v(hs_t, y1[:, None, :])
            )
            m_t = w2[..., None, None] * (
                hs_s[..., None, None] * y2[:, None] + ir.p_vv_t(hs_v, y1[:, None, :]) + hs_t
            )
            return jnp.concatenate(
                [m_s, m_v.reshape(m, -1), m_t.reshape(m, -1)], -1
            )

        agg = aggregate_2d(payload, edge_fn, src_l, dst_l, part, dcfg, op="sum")
        a = ir.Irreps(
            s=agg[:, :c],
            v=agg[:, c : 4 * c].reshape(n, c, 3),
            t=agg[:, 4 * c :].reshape(n, c, 3, 3),
        )
        mixed = ir.linear(a, lyr["w_s"], lyr["w_v"], lyr["w_t"])
        gates = _mlp(lyr["gate"], mixed.s)
        out = ir.gate(mixed, gates[:, :c], gates[:, c:])
        s_f, v_f, t_f = s_f + out.s, v_f + out.v, t_f + out.t
    return _mlp(params["readout"], s_f)


# ---------------------------------------------------------------------------
# shard_map train-step builder
# ---------------------------------------------------------------------------

_FWD_2D = {
    "graphcast": graphcast_2d,
    "gat-cora": gat_2d,
    "egnn": egnn_2d,
    "nequip": nequip_2d,
}


def build_2d_train_step(
    mesh: Mesh,
    model_cfg,
    part: Partition2D,
    e_cap: int,
    dcfg: Dist2DConfig | None = None,
    n_classes: int = 16,
):
    """Returns jit'd fn(params, nf, pos, src_l, dst_l, targets) -> (loss, grads).

    nf/pos/targets are owner-chunk sharded (R, C, s, .); edge blocks are
    (R, C, e_cap) with local indices, as produced by core.csr.partition_2d.
    """
    dcfg = dcfg or Dist2DConfig(
        row_axes=tuple(mesh.axis_names[:-1]), col_axis=mesh.axis_names[-1]
    )
    fwd = _FWD_2D[model_cfg.name]
    needs_pos = model_cfg.name in ("egnn", "nequip")

    def local(params, nf, pos, src_l, dst_l, targets):
        nf = nf.reshape(part.chunk, -1)
        pos = pos.reshape(part.chunk, -1)
        src_l = src_l.reshape(-1)
        dst_l = dst_l.reshape(-1)
        targets = targets.reshape(part.chunk)

        def loss_fn(p):
            if needs_pos:
                out = fwd(model_cfg, p, nf, pos, src_l, dst_l, part, dcfg)
            else:
                out = fwd(model_cfg, p, nf, src_l, dst_l, part, dcfg)
            logp = jax.nn.log_softmax(out.astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]
            return jax.lax.pmean(nll.mean(), dcfg.all_axes)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, dcfg.all_axes), grads)
        return loss, grads

    own = P(*dcfg.row_axes, dcfg.col_axis, None)
    own_flat = P(*dcfg.row_axes, dcfg.col_axis)
    in_specs = (P(), own, own, own, own, own_flat)
    mapped = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(mapped), in_specs
