"""Decoder-only transformer family (dense, GQA/MQA, MLA, fine-grained MoE).

Design notes (DESIGN.md §6):

* **scan-over-layers**: layer params carry a leading (L,) dim; the decode
  stack is one ``lax.scan`` body (+ optional ``jax.checkpoint``).  Keeps HLO
  small enough to dry-run 62-layer models on the 512-way mesh.
* **blockwise attention**: online-softmax over KV chunks (Rabe-Staats /
  flash-style) so 32k prefill never materializes S x S scores.
* **MLA** (DeepSeek-V2): low-rank KV latent cache; decode uses the absorbed
  form (q projected into latent space) so the cache stays (B, S, r + rope).
* **MoE**: GShard-style capacity dispatch with fine-grained routing groups
  (one-hot einsum — TPU-native, no dynamic scatter); optional shared experts
  (DeepSeek-V2) and int8-quantized dispatch payloads (beyond-paper).
* **sharding**: parameter PartitionSpecs from :func:`param_specs` — FSDP
  over the data axes, tensor parallelism over 'model'; activations batch-
  sharded over data, KV caches sequence-sharded over 'model'.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    # MoE (0 experts = dense)
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    moe_group: int = 512  # routing-group length (tokens)
    capacity_factor: float = 1.25
    # MLA (DeepSeek-V2)
    use_mla: bool = False
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_rope_dim: int = 64
    qk_nope_dim: int = 128
    v_head_dim: int = 128
    # misc
    act: str = "silu"  # silu (SwiGLU) | gelu (GeGLU)
    rope_theta: float = 10000.0
    q_chunk: int = 512
    kv_chunk: int = 1024
    scan_layers: bool = True
    remat: bool = True
    # 'nothing' = full per-layer recompute (flash-style; discards attention
    # score chunks in backward). 'dots' = save dot outputs — keeps the
    # blockwise-attention score tensors alive across the layer scan, which
    # costs TBs/device at 4k x 1M tokens (EXPERIMENTS.md §Perf iteration 1).
    remat_policy: str = "nothing"
    # rematerialize each q-chunk's online-softmax pass in backward (the
    # flash-attention backward strategy) instead of storing per-KV-chunk
    # probability tensors (§Perf iteration 2)
    attn_remat: bool = True
    # explicit sharding pins for MoE dispatch intermediates (set by the
    # launcher; empty = let GSPMD propagate). §Perf iteration 3.
    moe_dp_axes: tuple = ()
    moe_tp_axis: str = ""
    # expert weight layout: 'd' shards the model dim over FSDP axes (weights
    # re-gathered per layer); 'ff' shards d_ff_expert over FSDP axes so
    # expert weights stay resident and only activations reduce (§Perf it. 4)
    expert_shard: str = "d"
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up so embed/lm_head shard on any mesh axis
        (MiniCPM's 122753 is not divisible by 16); pad logits are masked."""
        return -(-self.vocab // 256) * 256

    @property
    def qk_head_dim(self) -> int:
        return (self.qk_nope_dim + self.qk_rope_dim) if self.use_mla else self.head_dim

    @property
    def cache_width(self) -> int:
        """Per-token KV cache width (the MLA memory win shows up here)."""
        if self.use_mla:
            return self.kv_lora_rank + self.qk_rope_dim
        return 2 * self.n_kv_heads * self.head_dim

    def n_params(self) -> int:
        """Analytic parameter count (for 6ND roofline accounting)."""
        d, l = self.d_model, self.n_layers
        if self.use_mla:
            q_in = (
                self.q_lora_rank * (d + self.n_heads * self.qk_head_dim)
                if self.q_lora_rank
                else d * self.n_heads * self.qk_head_dim
            )
            attn = (
                q_in
                + d * (self.kv_lora_rank + self.qk_rope_dim)
                + self.kv_lora_rank * self.n_heads * (self.qk_nope_dim + self.v_head_dim)
                + self.n_heads * self.v_head_dim * d
            )
        else:
            attn = d * self.n_heads * self.head_dim * 2 + d * self.n_kv_heads * self.head_dim * 2
        if self.is_moe:
            ffn = d * self.n_experts + 3 * d * self.d_ff_expert * (
                self.n_experts + self.n_shared_experts
            )
        else:
            ffn = 3 * d * self.d_ff
        return l * (attn + ffn + 2 * d) + 2 * self.vocab * d + d

    def n_active_params(self) -> int:
        """Params touched per token (MoE: routed top-k + shared only)."""
        if not self.is_moe:
            return self.n_params()
        d, l = self.d_model, self.n_layers
        full = self.n_params()
        ffn_all = 3 * d * self.d_ff_expert * (self.n_experts + self.n_shared_experts)
        ffn_act = 3 * d * self.d_ff_expert * (self.top_k + self.n_shared_experts)
        return full - l * (ffn_all - ffn_act)


# ---------------------------------------------------------------------------
# init + sharding specs
# ---------------------------------------------------------------------------


def _dense(key, shape, dtype, scale_axis=0):
    scale = 1.0 / max(shape[scale_axis], 1) ** 0.5
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_params(cfg: TransformerConfig, key: jax.Array) -> Params:
    keys = iter(jax.random.split(key, 64))
    d, l, dt = cfg.d_model, cfg.n_layers, cfg.param_dtype

    def stack(shape):
        return (l,) + shape if cfg.scan_layers else (l,) + shape

    layer: Params = {
        "ln1": jnp.ones(stack((d,)), dt),
        "ln2": jnp.ones(stack((d,)), dt),
    }
    if cfg.use_mla:
        if cfg.q_lora_rank:
            layer["wq_a"] = _dense(next(keys), stack((d, cfg.q_lora_rank)), dt, 1)
            layer["wq_b"] = _dense(
                next(keys), stack((cfg.q_lora_rank, cfg.n_heads * cfg.qk_head_dim)), dt, 1
            )
        else:
            layer["wq"] = _dense(next(keys), stack((d, cfg.n_heads * cfg.qk_head_dim)), dt, 1)
        layer["wkv_a"] = _dense(
            next(keys), stack((d, cfg.kv_lora_rank + cfg.qk_rope_dim)), dt, 1
        )
        layer["wkv_b"] = _dense(
            next(keys),
            stack((cfg.kv_lora_rank, cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim))),
            dt,
            1,
        )
        layer["wo"] = _dense(next(keys), stack((cfg.n_heads * cfg.v_head_dim, d)), dt, 1)
    else:
        layer["wq"] = _dense(next(keys), stack((d, cfg.n_heads * cfg.head_dim)), dt, 1)
        layer["wk"] = _dense(next(keys), stack((d, cfg.n_kv_heads * cfg.head_dim)), dt, 1)
        layer["wv"] = _dense(next(keys), stack((d, cfg.n_kv_heads * cfg.head_dim)), dt, 1)
        layer["wo"] = _dense(next(keys), stack((cfg.n_heads * cfg.head_dim, d)), dt, 1)
    if cfg.is_moe:
        e, fe = cfg.n_experts, cfg.d_ff_expert
        layer["router"] = _dense(next(keys), stack((d, e)), dt, 1)
        layer["we_gate"] = _dense(next(keys), stack((e, d, fe)), dt, 2)
        layer["we_up"] = _dense(next(keys), stack((e, d, fe)), dt, 2)
        layer["we_down"] = _dense(next(keys), stack((e, fe, d)), dt, 2)
        if cfg.n_shared_experts:
            fs = cfg.n_shared_experts * fe
            layer["ws_gate"] = _dense(next(keys), stack((d, fs)), dt, 1)
            layer["ws_up"] = _dense(next(keys), stack((d, fs)), dt, 1)
            layer["ws_down"] = _dense(next(keys), stack((fs, d)), dt, 1)
    else:
        layer["w_gate"] = _dense(next(keys), stack((d, cfg.d_ff)), dt, 1)
        layer["w_up"] = _dense(next(keys), stack((d, cfg.d_ff)), dt, 1)
        layer["w_down"] = _dense(next(keys), stack((cfg.d_ff, d)), dt, 1)

    return {
        "embed": _dense(next(keys), (cfg.padded_vocab, d), dt, 1),
        "layers": layer,
        "final_norm": jnp.ones((d,), dt),
        "lm_head": _dense(next(keys), (d, cfg.padded_vocab), dt, 0),
    }


def param_specs(cfg: TransformerConfig, fsdp: tuple[str, ...] = ("data",), tp: str = "model"):
    """PartitionSpec pytree matching init_params (FSDP x TP)."""
    f = fsdp if len(fsdp) > 1 else fsdp[0]
    layer: dict[str, P] = {"ln1": P(None, None), "ln2": P(None, None)}
    two_d = P(None, f, tp)  # (L, d_in, d_out): FSDP on in, TP on out
    out_proj = P(None, tp, f)  # (L, h, d): TP on in, FSDP on out
    if cfg.use_mla:
        if cfg.q_lora_rank:
            layer["wq_a"] = P(None, f, None)
            layer["wq_b"] = P(None, None, tp)
        else:
            layer["wq"] = two_d
        layer["wkv_a"] = P(None, f, None)
        layer["wkv_b"] = P(None, None, tp)
        layer["wo"] = out_proj
    else:
        layer.update(wq=two_d, wk=two_d, wv=two_d, wo=out_proj)
    if cfg.is_moe:
        layer["router"] = P(None, f, None)
        if cfg.expert_shard == "ff":
            # experts over TP, d_ff over FSDP: weights stay resident,
            # down-proj partial sums psum over the FSDP axes
            layer["we_gate"] = P(None, tp, None, f)
            layer["we_up"] = P(None, tp, None, f)
            layer["we_down"] = P(None, tp, f, None)
        else:
            layer["we_gate"] = P(None, tp, f, None)
            layer["we_up"] = P(None, tp, f, None)
            layer["we_down"] = P(None, tp, None, f)
        if cfg.n_shared_experts:
            layer.update(ws_gate=two_d, ws_up=two_d, ws_down=out_proj)
    else:
        layer.update(w_gate=two_d, w_up=two_d, w_down=out_proj)
    return {
        "embed": P(tp, f),  # vocab over TP -> masked-psum lookup
        "layers": layer,
        "final_norm": P(None),
        "lm_head": P(f, tp),  # logits vocab-sharded over TP
    }


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def rmsnorm(x, w, eps=1e-6):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope(x, pos, theta):
    """x: (..., S, H, hd) with even hd; pos: (..., S)."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[..., :, None, None].astype(jnp.float32) * freqs  # (..., S, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)


def _act(cfg, g):
    return jax.nn.gelu(g) if cfg.act == "gelu" else jax.nn.silu(g)


def blockwise_attention(
    q, k, v, *, causal: bool, q_chunk: int, kv_chunk: int, remat_chunks: bool = True
):
    """Online-softmax attention; q (B,S,H,hd), k/v (B,T,KV,hd_v). GQA-aware.

    Never materializes (S, T) scores: scans KV in chunks carrying
    (max, sum, acc) per q position.  Causal masking by absolute position.
    """
    b, s, h, hd = q.shape
    t, kvh = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]
    g = h // kvh  # query heads per kv head
    scale = hd**-0.5
    q_chunk = min(q_chunk, s)
    kv_chunk = min(kv_chunk, t)
    s_pad = -(-s // q_chunk) * q_chunk
    t_pad = -(-t // kv_chunk) * kv_chunk
    q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    nq, nk = s_pad // q_chunk, t_pad // kv_chunk

    qb = q.reshape(b, nq, q_chunk, kvh, g, hd)
    kb = k.reshape(b, nk, kv_chunk, kvh, hd)
    vb = v.reshape(b, nk, kv_chunk, kvh, hd_v)
    q_pos = jnp.arange(s_pad).reshape(nq, q_chunk)
    # padded KV positions pushed past every query so they never attend
    k_pos_flat = jnp.where(jnp.arange(t_pad) < t, jnp.arange(t_pad), s_pad + t_pad)
    k_pos = k_pos_flat.reshape(nk, kv_chunk)

    def per_q_chunk(q_i, qpos_i):
        # q_i: (b, q_chunk, kvh, g, hd)
        def body(carry, inp):
            m, l, acc = carry
            k_j, v_j, kpos_j = inp
            logits = (
                jnp.einsum(
                    "bqkgd,btkd->bkgqt", q_i.astype(jnp.float32), k_j.astype(jnp.float32)
                )
                * scale
            )
            mask = kpos_j[None, :] < (s_pad + t_pad)  # drop padded KV
            if causal:
                mask = mask & (qpos_i[:, None] >= kpos_j[None, :])
            logits = jnp.where(mask[None, None, None], logits, -1e30)
            m_new = jnp.maximum(m, logits.max(-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p, v_j.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((b, kvh, g, q_chunk), -jnp.inf, jnp.float32),
            jnp.zeros((b, kvh, g, q_chunk), jnp.float32),
            jnp.zeros((b, kvh, g, q_chunk, hd_v), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(body, init, (kb.swapaxes(0, 1), vb.swapaxes(0, 1), k_pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4)  # (b, q_chunk, kvh, g, hd_v)

    chunk_fn = per_q_chunk
    if remat_chunks:
        chunk_fn = jax.checkpoint(
            per_q_chunk, policy=jax.checkpoint_policies.nothing_saveable
        )
    out = jax.lax.map(lambda args: chunk_fn(*args), (qb.swapaxes(0, 1), q_pos))
    out = out.swapaxes(0, 1).reshape(b, s_pad, h, hd_v)
    return out[:, :s]


# ---------------------------------------------------------------------------
# attention variants (train/prefill path)
# ---------------------------------------------------------------------------


def _attention(cfg: TransformerConfig, lp: Params, x, pos):
    b, s, d = x.shape
    cdt = cfg.compute_dtype
    if cfg.use_mla:
        if cfg.q_lora_rank:
            q = (x @ lp["wq_a"].astype(cdt)) @ lp["wq_b"].astype(cdt)
        else:
            q = x @ lp["wq"].astype(cdt)
        q = q.reshape(b, s, cfg.n_heads, cfg.qk_head_dim)
        q_nope, q_rope = q[..., : cfg.qk_nope_dim], q[..., cfg.qk_nope_dim :]
        q_rope = rope(q_rope, pos, cfg.rope_theta)
        kv = x @ lp["wkv_a"].astype(cdt)  # (b, s, r + rope)
        latent, k_rope = kv[..., : cfg.kv_lora_rank], kv[..., cfg.kv_lora_rank :]
        k_rope = rope(k_rope[:, :, None, :], pos, cfg.rope_theta)  # shared head
        kvu = latent @ lp["wkv_b"].astype(cdt)  # (b, s, H*(nope+v))
        kvu = kvu.reshape(b, s, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim)
        k_nope, v = kvu[..., : cfg.qk_nope_dim], kvu[..., cfg.qk_nope_dim :]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, cfg.n_heads, cfg.qk_rope_dim))], -1
        )
        q = jnp.concatenate([q_nope, q_rope], -1)
        o = blockwise_attention(
            q, k, v, causal=True, q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
            remat_chunks=cfg.attn_remat,
        )
        o = o.reshape(b, s, cfg.n_heads * cfg.v_head_dim).astype(cdt)
        return o @ lp["wo"].astype(cdt)
    # GQA / MQA / MHA
    q = (x @ lp["wq"].astype(cdt)).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = (x @ lp["wk"].astype(cdt)).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ lp["wv"].astype(cdt)).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)
    o = blockwise_attention(
        q, k, v, causal=True, q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
        remat_chunks=cfg.attn_remat,
    )
    o = o.reshape(b, s, cfg.n_heads * cfg.head_dim).astype(cdt)
    return o @ lp["wo"].astype(cdt)


# ---------------------------------------------------------------------------
# FFN / MoE
# ---------------------------------------------------------------------------


def _dense_ffn(cfg, lp, x):
    cdt = cfg.compute_dtype
    g = _act(cfg, x @ lp["w_gate"].astype(cdt))
    u = x @ lp["w_up"].astype(cdt)
    return (g * u) @ lp["w_down"].astype(cdt)


def _moe_ffn(cfg: TransformerConfig, lp: Params, x):
    """GShard capacity dispatch with fine-grained routing groups."""
    types = jax.sharding.get_abstract_mesh().axis_types
    if cfg.moe_dp_axes and types and all(
        t == jax.sharding.AxisType.Explicit for t in types
    ):
        # the dispatch pins are Auto-mode sharding hints (under Explicit
        # axes ``with_sharding_constraint`` only asserts): run the body
        # with the mesh axes switched to Auto
        out = (jax.typeof(x).sharding.spec, P())
        return jax.sharding.auto_axes(
            functools.partial(_moe_ffn_body, cfg), out_sharding=out
        )(lp, x)
    return _moe_ffn_body(cfg, lp, x)


def _moe_ffn_body(cfg: TransformerConfig, lp: Params, x):
    cdt = cfg.compute_dtype
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    tokens = x.reshape(-1, d)
    t = tokens.shape[0]
    gsz = min(cfg.moe_group, t)
    t_pad = -(-t // gsz) * gsz
    tokens = jnp.pad(tokens, ((0, t_pad - t), (0, 0)))
    ng = t_pad // gsz
    cap = min(max(int(gsz * k * cfg.capacity_factor / e), 1), gsz)
    xt = tokens.reshape(ng, gsz, d)

    def pin(arr, *spec):
        if cfg.moe_dp_axes:
            dp = cfg.moe_dp_axes if len(cfg.moe_dp_axes) > 1 else cfg.moe_dp_axes[0]
            resolved = [dp if a == "dp" else (cfg.moe_tp_axis or None) if a == "tp" else None for a in spec]
            return jax.lax.with_sharding_constraint(
                arr, jax.sharding.PartitionSpec(*resolved)
            )
        return arr

    logits = (xt @ lp["router"].astype(cdt)).astype(jnp.float32)  # (ng, gsz, e)
    gates = jax.nn.softmax(logits, axis=-1)
    top_g, top_e = jax.lax.top_k(gates, k)  # (ng, gsz, k)
    top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)

    onehot = jax.nn.one_hot(top_e, e, dtype=jnp.float32)  # (ng, gsz, k, e)
    onehot = pin(onehot, "dp", None, None, "tp")
    # position of each (token, choice) in its expert buffer
    pos = jnp.cumsum(onehot.reshape(ng, gsz * k, e), axis=1).reshape(ng, gsz, k, e) - 1.0
    keep = (pos < cap) * onehot
    # per-choice buffer position (gathered along e) -> no 5D (k,e,cap) tensor
    pos_k = jnp.take_along_axis(pos, top_e[..., None].astype(jnp.int32), axis=-1)[..., 0]
    cap_oh = jax.nn.one_hot(pos_k, cap, dtype=jnp.float32)  # (ng, gsz, k, cap)
    dispatch = jnp.einsum("gske,gskc->gsec", keep, cap_oh)  # (ng, gsz, e, cap)
    combine = jnp.einsum("gske,gskc->gsec", keep * top_g[..., None], cap_oh)
    dispatch = pin(dispatch, "dp", None, "tp", None)
    combine = pin(combine, "dp", None, "tp", None)

    xin = jnp.einsum("gsec,gsd->gecd", dispatch.astype(cdt), xt)  # (ng, e, cap, d)
    xin = pin(xin, "dp", "tp", None, None)
    w_gate, w_up, w_down = lp["we_gate"], lp["we_up"], lp["we_down"]
    if cfg.expert_shard == "ff" and cfg.moe_dp_axes:
        # keep expert weights resident: experts over TP, d_ff over DP axes
        dp = cfg.moe_dp_axes if len(cfg.moe_dp_axes) > 1 else cfg.moe_dp_axes[0]
        wspec = jax.sharding.PartitionSpec(cfg.moe_tp_axis or None, None, dp)
        dspec = jax.sharding.PartitionSpec(cfg.moe_tp_axis or None, dp, None)
        w_gate = jax.lax.with_sharding_constraint(w_gate, wspec)
        w_up = jax.lax.with_sharding_constraint(w_up, wspec)
        w_down = jax.lax.with_sharding_constraint(w_down, dspec)
    hg = _act(cfg, jnp.einsum("gecd,edf->gecf", xin, w_gate.astype(cdt)))
    hu = jnp.einsum("gecd,edf->gecf", xin, w_up.astype(cdt))
    hout = jnp.einsum("gecf,efd->gecd", hg * hu, w_down.astype(cdt))
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(cdt), hout)

    if cfg.n_shared_experts:
        gsh = _act(cfg, xt @ lp["ws_gate"].astype(cdt))
        ush = xt @ lp["ws_up"].astype(cdt)
        y = y + (gsh * ush) @ lp["ws_down"].astype(cdt)
    # aux load-balance loss (GShard): mean fraction^2 per expert
    me = onehot.sum(2).mean(1)  # (ng, e) token fraction
    ce = gates.mean(1)
    aux = (me * ce).sum(-1).mean() * e
    return y.reshape(-1, d)[:t].reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------


def _layer(cfg: TransformerConfig, lp: Params, x, pos):
    h = x + _attention(cfg, lp, rmsnorm(x, lp["ln1"]), pos)
    ff_in = rmsnorm(h, lp["ln2"])
    if cfg.is_moe:
        ff, aux = _moe_ffn(cfg, lp, ff_in)
    else:
        ff, aux = _dense_ffn(cfg, lp, ff_in), jnp.float32(0)
    return h + ff, aux


def forward(cfg: TransformerConfig, params: Params, tokens) -> tuple[jax.Array, jax.Array]:
    """tokens (B, S) -> (logits (B, S, V), aux_loss)."""
    cdt = cfg.compute_dtype
    b, s = tokens.shape
    x = params["embed"].astype(cdt)[tokens]  # gather; GSPMD handles vocab shard
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))

    layer_fn = functools.partial(_layer, cfg)
    if cfg.remat:
        policy = (
            jax.checkpoint_policies.nothing_saveable
            if cfg.remat_policy == "nothing"
            else jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
        layer_fn = jax.checkpoint(layer_fn, policy=policy)

    def scan_body(carry, lp):
        y, aux = layer_fn(lp, carry, pos)
        return y, aux

    if cfg.scan_layers:
        x, auxs = jax.lax.scan(scan_body, x, params["layers"])
        aux = auxs.sum()
    else:
        aux = jnp.float32(0)
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda p: p[i], params["layers"])
            x, a = layer_fn(lp, x, pos)
            aux = aux + a
    x = rmsnorm(x, params["final_norm"])
    logits = x @ params["lm_head"].astype(cdt)
    if cfg.padded_vocab != cfg.vocab:  # mask pad logits out of the softmax
        pad_mask = (jnp.arange(cfg.padded_vocab) >= cfg.vocab) * jnp.asarray(
            -1e9, logits.dtype
        )
        logits = logits + pad_mask
    return logits, aux


def loss_fn(cfg: TransformerConfig, params: Params, batch) -> jax.Array:
    """Next-token cross entropy (+0.01 * MoE aux)."""
    tokens = batch["tokens"]
    logits, aux = forward(cfg, params, tokens[:, :-1])
    targets = tokens[:, 1:]
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (lse - gold).mean() + 0.01 * aux


# ---------------------------------------------------------------------------
# serving: prefill + decode with KV cache
# ---------------------------------------------------------------------------


def init_cache(cfg: TransformerConfig, batch: int, max_seq: int, dtype=None):
    """(L, B, S, cache_width) — MLA stores the compressed latent + rope key."""
    dtype = dtype or cfg.compute_dtype
    return jnp.zeros((cfg.n_layers, batch, max_seq, cfg.cache_width), dtype)


def cache_spec(fsdp=("data",), tp: str = "model") -> P:
    f = fsdp if len(fsdp) > 1 else fsdp[0]
    return P(None, f, tp, None)  # batch over FSDP axes, seq over TP


def _decode_attention(cfg: TransformerConfig, lp: Params, x, cache_l, pos):
    """One-token attention against a (B, S, cache_width) cache layer.

    Returns (out (B, 1, d), updated cache layer).  ``pos``: (B,) int32
    current positions.
    """
    b = x.shape[0]
    cdt = cfg.compute_dtype
    s_max = cache_l.shape[1]
    t_pos = jnp.arange(s_max)
    live = t_pos[None, :] <= pos[:, None]  # (B, S)

    if cfg.use_mla:
        if cfg.q_lora_rank:
            q = (x @ lp["wq_a"].astype(cdt)) @ lp["wq_b"].astype(cdt)
        else:
            q = x @ lp["wq"].astype(cdt)
        q = q.reshape(b, cfg.n_heads, cfg.qk_head_dim)
        q_nope, q_rope = q[..., : cfg.qk_nope_dim], q[..., cfg.qk_nope_dim :]
        q_rope = rope(q_rope[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        kv = (x @ lp["wkv_a"].astype(cdt))[:, None, :]  # (B,1,r+rope)
        k_rope_new = rope(
            kv[..., cfg.kv_lora_rank :][:, :, None, :], pos[:, None], cfg.rope_theta
        )[:, :, 0, :]
        new_entry = jnp.concatenate([kv[..., : cfg.kv_lora_rank], k_rope_new], -1)
        cache_l = _scatter_cache(cache_l, new_entry[:, 0], pos)
        latent = cache_l[..., : cfg.kv_lora_rank]  # (B, S, r)
        k_rope = cache_l[..., cfg.kv_lora_rank :]  # (B, S, rope)
        # absorbed scores: q_nope -> latent space via wkv_b's k-part
        wkv_b = lp["wkv_b"].astype(cdt).reshape(
            cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim
        )
        w_uk = wkv_b[..., : cfg.qk_nope_dim]  # (r, H, nope)
        w_uv = wkv_b[..., cfg.qk_nope_dim :]  # (r, H, v)
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32))
        scores = jnp.einsum("bhr,bsr->bhs", q_lat, latent.astype(jnp.float32))
        scores += jnp.einsum(
            "bhp,bsp->bhs", q_rope.astype(jnp.float32), k_rope.astype(jnp.float32)
        )
        scores *= cfg.qk_head_dim**-0.5
        scores = jnp.where(live[:, None], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        ctx_lat = jnp.einsum("bhs,bsr->bhr", w, latent.astype(jnp.float32))
        o = jnp.einsum("bhr,rhv->bhv", ctx_lat, w_uv.astype(jnp.float32))
        o = o.reshape(b, 1, cfg.n_heads * cfg.v_head_dim).astype(cdt)
        return o @ lp["wo"].astype(cdt), cache_l

    q = (x @ lp["wq"].astype(cdt)).reshape(b, cfg.n_heads, cfg.head_dim)
    k_new = (x @ lp["wk"].astype(cdt)).reshape(b, cfg.n_kv_heads, cfg.head_dim)
    v_new = (x @ lp["wv"].astype(cdt)).reshape(b, cfg.n_kv_heads, cfg.head_dim)
    q = rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    k_new = rope(k_new[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    new_entry = jnp.concatenate([k_new.reshape(b, -1), v_new.reshape(b, -1)], -1)
    cache_l = _scatter_cache(cache_l, new_entry, pos)
    kc = cache_l[..., : cfg.n_kv_heads * cfg.head_dim].reshape(
        b, s_max, cfg.n_kv_heads, cfg.head_dim
    )
    vc = cache_l[..., cfg.n_kv_heads * cfg.head_dim :].reshape(
        b, s_max, cfg.n_kv_heads, cfg.head_dim
    )
    g = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, cfg.n_kv_heads, g, cfg.head_dim)
    scores = (
        jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32), kc.astype(jnp.float32))
        * cfg.head_dim**-0.5
    )
    scores = jnp.where(live[:, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", w, vc.astype(jnp.float32))
    o = o.reshape(b, 1, cfg.n_heads * cfg.head_dim).astype(cdt)
    return o @ lp["wo"].astype(cdt), cache_l


def _scatter_cache(cache_l, new_entry, pos):
    """cache_l (B,S,W) <- new_entry (B,W) at per-row positions pos (B,)."""
    onehot = jax.nn.one_hot(pos, cache_l.shape[1], dtype=cache_l.dtype)  # (B,S)
    return cache_l * (1 - onehot[..., None]) + onehot[..., None] * new_entry[:, None, :]


def _decode_ffn(cfg, lp, x):
    if cfg.is_moe:
        y, _ = _moe_ffn(cfg, lp, x)
        return y
    return _dense_ffn(cfg, lp, x)


def decode_step(cfg: TransformerConfig, params: Params, cache, tokens, pos):
    """One decode step. tokens (B,) int32, pos (B,) int32 -> (logits, cache)."""
    cdt = cfg.compute_dtype
    x = params["embed"].astype(cdt)[tokens][:, None, :]  # (B,1,d)

    def body(x, inp):
        lp, cache_l = inp
        attn, cache_new = _decode_attention(
            cfg, lp, rmsnorm(x, lp["ln1"])[:, 0], cache_l, pos
        )
        h = x + attn
        h = h + _decode_ffn(cfg, lp, rmsnorm(h, lp["ln2"]))
        return h, cache_new

    if cfg.scan_layers:
        x, cache = jax.lax.scan(body, x, (params["layers"], cache))
    else:
        caches = []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda p: p[i], params["layers"])
            x, c = body(x, (lp, cache[i]))
            caches.append(c)
        cache = jnp.stack(caches)
    x = rmsnorm(x, params["final_norm"])
    logits = (x @ params["lm_head"].astype(cdt))[:, 0]
    if cfg.padded_vocab != cfg.vocab:
        logits = logits + (jnp.arange(cfg.padded_vocab) >= cfg.vocab) * jnp.asarray(
            -1e9, logits.dtype
        )
    return logits, cache


def prefill(cfg: TransformerConfig, params: Params, tokens):
    """Prefill pass: full forward returning last-position logits (cache fill
    is exercised by the decode path; prefill cells measure the forward)."""
    logits, _ = forward(cfg, params, tokens)
    return logits[:, -1]
