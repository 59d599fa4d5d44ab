"""Transformer / SSM / MoE block implementations.

Every block family exposes:

* ``<family>_specs(cfg)``   -> ParamSpec tree for one layer;
* ``<family>_fwd(p, x, ...)``  -> sequence forward (train / prefill). In
  prefill mode it also returns the per-layer cache entries;
* ``<family>_decode(p, x, cache, ...)`` -> single-token forward + new cache.

All matmul weights carry logical axes so the sharding rule tables in
``repro.parallel.sharding`` place them on the mesh; activations get
``constrain`` hints at block boundaries and GSPMD inserts the collectives.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import (
    Window, act_fn, apply_rope, attention, decode_attention, rms_norm)
from repro.models.param import ParamSpec, spec
from repro.parallel.sharding import constrain


# ==========================================================================
# Dense / GQA attention
# ==========================================================================

def attn_specs(cfg: ArchConfig) -> dict:
    D, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s: dict[str, Any] = {
        "ln": spec((D,), ("embed",), init="ones"),
        "wq": spec((D, H * hd), ("embed", "q_heads")),
        "wk": spec((D, Hkv * hd), ("embed", "kv_heads")),
        "wv": spec((D, Hkv * hd), ("embed", "kv_heads")),
        "wo": spec((H * hd, D), ("q_heads", "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = spec((H * hd,), ("q_heads",), init="zeros")
        s["bk"] = spec((Hkv * hd,), ("kv_heads",), init="zeros")
        s["bv"] = spec((Hkv * hd,), ("kv_heads",), init="zeros")
    if cfg.qk_norm:
        s["q_norm"] = spec((hd,), (None,), init="ones")
        s["k_norm"] = spec((hd,), (None,), init="ones")
    return s


def _qkv(p, x, cfg: ArchConfig, positions):
    B, S, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, Hkv, hd)
    v = v.reshape(B, S, Hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if not cfg.encoder_only:  # encoder (hubert) uses learned/conv pos, stubbed
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    return q, k, v


def attn_fwd(p, x, cfg: ArchConfig, *, window: Window = None,
             prefix_len: int = 0, return_cache: bool = False):
    """x: (B, S, D) -> (B, S, D) [+ (k, v) cache entries]."""
    B, S, _ = x.shape
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    positions = jnp.arange(S)
    q, k, v = _qkv(p, h, cfg, positions)
    causal = not cfg.encoder_only
    o = attention(q, k, v, causal=causal, window=window,
                  prefix_len=prefix_len)
    o = o.reshape(B, S, cfg.n_heads * cfg.head_dim)
    out = o @ p["wo"]
    out = constrain(out, "batch", "seq", "embed")
    if return_cache:
        return out, (k, v)
    return out


def attn_decode(p, x, k_cache, v_cache, cache_len, cfg: ArchConfig, *,
                window: Window = None, prefix_len: int = 0):
    """x: (B, 1, D); caches: (B, Smax, Hkv, hd). Returns out, new caches."""
    B = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    positions = jnp.full((1,), cache_len, jnp.int32)
    q, k, v = _qkv(p, h, cfg, positions)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k.astype(k_cache.dtype), cache_len, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v.astype(v_cache.dtype), cache_len, axis=1)
    o = decode_attention(q, k_cache, v_cache, cache_len, window=window,
                         prefix_len=prefix_len)
    out = o.reshape(B, 1, H * hd) @ p["wo"]
    return constrain(out, "batch", None, "embed"), k_cache, v_cache


# ==========================================================================
# MLA (DeepSeek-V2 multi-head latent attention)
# ==========================================================================

def mla_specs(cfg: ArchConfig) -> dict:
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    qh = m.nope_head_dim + m.rope_head_dim
    return {
        "ln": spec((D,), ("embed",), init="ones"),
        "q_a": spec((D, m.q_lora_rank), ("embed", "q_lora")),
        "q_a_norm": spec((m.q_lora_rank,), ("q_lora",), init="ones"),
        "q_b": spec((m.q_lora_rank, H * qh), ("q_lora", "q_heads")),
        "kv_a": spec((D, m.kv_lora_rank + m.rope_head_dim),
                     ("embed", "kv_lora")),
        "kv_a_norm": spec((m.kv_lora_rank,), ("kv_lora",), init="ones"),
        "kv_b": spec((m.kv_lora_rank, H * (m.nope_head_dim + m.v_head_dim)),
                     ("kv_lora", "q_heads")),
        "wo": spec((H * m.v_head_dim, D), ("q_heads", "embed")),
    }


def _mla_q(p, h, cfg: ArchConfig, positions):
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = h.shape
    q = rms_norm(h @ p["q_a"], p["q_a_norm"], cfg.norm_eps) @ p["q_b"]
    q = q.reshape(B, S, H, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(p, h, cfg: ArchConfig, positions):
    m = cfg.mla
    ckv = h @ p["kv_a"]  # (B, S, kv_lora + rope)
    c, k_rope = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    c = rms_norm(c, p["kv_a_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]
    return c, k_rope


def mla_fwd(p, x, cfg: ArchConfig, *, return_cache: bool = False):
    """Non-absorbed MLA (train / prefill): materialize per-head K/V."""
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    positions = jnp.arange(S)
    q_nope, q_rope = _mla_q(p, h, cfg, positions)
    c, k_rope = _mla_ckv(p, h, cfg, positions)
    kv = (c @ p["kv_b"]).reshape(B, S, H, m.nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.nope_head_dim], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.rope_head_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    o = attention(q, k, v, causal=True, scale=scale)
    out = o.reshape(B, S, H * m.v_head_dim) @ p["wo"]
    out = constrain(out, "batch", "seq", "embed")
    if return_cache:
        return out, (c, k_rope)  # compressed cache: kv_lora + rope dims only
    return out


def mla_decode(p, x, c_cache, krope_cache, cache_len, cfg: ArchConfig):
    """Absorbed MLA decode: scores/values computed in the latent space.

    caches: c (B, Smax, kv_lora), k_rope (B, Smax, rope_dim). This is the
    memory win of MLA — the per-head K/V are never materialized at decode.
    """
    m, H = cfg.mla, cfg.n_heads
    B = x.shape[0]
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    positions = jnp.full((1,), cache_len, jnp.int32)
    q_nope, q_rope = _mla_q(p, h, cfg, positions)  # (B,1,H,·)
    c, k_rope = _mla_ckv(p, h, cfg, positions)
    c_cache = jax.lax.dynamic_update_slice_in_dim(
        c_cache, c.astype(c_cache.dtype), cache_len, axis=1)
    krope_cache = jax.lax.dynamic_update_slice_in_dim(
        krope_cache, k_rope.astype(krope_cache.dtype), cache_len, axis=1)

    # absorb kv_b into q: q_lat[h] = q_nope[h] @ W_uk[h]^T  (per head)
    w_kv = p["kv_b"].reshape(m.kv_lora_rank, H, m.nope_head_dim + m.v_head_dim)
    w_uk = w_kv[:, :, :m.nope_head_dim]      # (lora, H, nope)
    w_uv = w_kv[:, :, m.nope_head_dim:]      # (lora, H, v)
    q_lat = jnp.einsum("bqhn,lhn->bhql", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))  # (B,H,1,lora)

    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    s = jnp.einsum("bhql,bkl->bhqk", q_lat,
                   c_cache.astype(jnp.float32))
    s = s + jnp.einsum("bqhr,bkr->bhqk", q_rope.astype(jnp.float32),
                       krope_cache.astype(jnp.float32))
    s = s * scale
    k_pos = jnp.arange(c_cache.shape[1])
    s = s + jnp.where(k_pos <= cache_len, 0.0, -1e30)
    s = constrain(s, "batch", "heads", None, "kv_seq")
    prob = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhqk,bkl->bhql", prob, c_cache.astype(jnp.float32))
    o = jnp.einsum("bhql,lhv->bqhv", o_lat, w_uv.astype(jnp.float32))
    out = o.reshape(B, 1, H * m.v_head_dim).astype(x.dtype) @ p["wo"]
    return constrain(out, "batch", None, "embed"), c_cache, krope_cache


# ==========================================================================
# MLPs (dense)
# ==========================================================================

def mlp_specs(cfg: ArchConfig, d_ff: Optional[int] = None) -> dict:
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    s = {"ln": spec((D,), ("embed",), init="ones")}
    if cfg.act in ("silu", "gelu_glu"):  # gated (SwiGLU / GeGLU)
        s["wg"] = spec((D, F), ("embed", "mlp"))
        s["wu"] = spec((D, F), ("embed", "mlp"))
        s["wd"] = spec((F, D), ("mlp", "embed"))
    else:  # plain 2-layer (hubert)
        s["w1"] = spec((D, F), ("embed", "mlp"))
        s["w2"] = spec((F, D), ("mlp", "embed"))
    return s


def mlp_fwd(p, x, cfg: ArchConfig):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    a = act_fn(cfg.act)
    if "wg" in p:
        y = (a(h @ p["wg"]) * (h @ p["wu"])) @ p["wd"]
    else:
        y = a(h @ p["w1"]) @ p["w2"]
    return constrain(y, "batch", "seq", "embed")


# ==========================================================================
# MoE (sort-free GShard-style dispatch; honest FLOPs)
# ==========================================================================

# §Perf hillclimb: shard_map dispatch. GSPMD lowers the scatter from
# data-sharded tokens into the expert-sharded buffer as a replicated
# partial-buffer all-reduce (16x the needed bytes). With shard_map, every
# model-rank selects ITS experts' tokens locally (tokens are replicated
# across the model axis anyway) and the combine is one (G, D) psum that
# merges with the block's existing TP all-reduce. Expert weights are
# all-gathered over the FSDP axis ONCE per layer, outside the group scan.
MOE_SHARD_MAP = {"enabled": False}


def _moe_group_smap_fn(cfg: ArchConfig, n_model: int, batch_axes):
    mo = cfg.moe
    E, K = mo.n_experts, mo.top_k
    E_loc = E // n_model

    def f(tok, router, wg, wu, wd):
        # tok: (G_loc, D) — this data-shard's tokens, replicated over model
        # wg/wu/wd: (E_loc, D, F) — this model-rank's experts
        G, D = tok.shape
        C = max(8, int(math.ceil(G * K * mo.capacity_factor / E / 8.0)) * 8)
        r = jax.lax.axis_index("model")
        logits = tok.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, topk_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[topk_idx.reshape(-1)].add(
            1.0 / (G * K))
        aux = E * jnp.sum(me * ce)

        flat_e = topk_idx.reshape(-1)
        sel = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        ranks = jnp.cumsum(sel, axis=0) - sel
        pos = jnp.sum(ranks * sel, axis=-1)
        mine = (flat_e // E_loc) == r
        keep = (pos < C) & mine
        le = jnp.where(mine, flat_e % E_loc, E_loc)     # E_loc = drop row
        pos_c = jnp.where(keep, pos, C)
        src_tok = jnp.arange(G * K) // K

        buf = jnp.zeros((E_loc + 1, C + 1, D), tok.dtype)
        buf = buf.at[le, pos_c].add(tok[src_tok])
        xin = buf[:E_loc, :C, :]
        a = act_fn(cfg.act)
        hmid = a(jnp.einsum("ecd,edf->ecf", xin, wg)) * \
            jnp.einsum("ecd,edf->ecf", xin, wu)
        hout = jnp.einsum("ecf,efd->ecd", hmid, wd)
        hpad = jnp.pad(hout, ((0, 1), (0, 1), (0, 0)))
        picked = hpad[le, pos_c].astype(jnp.float32) \
            * gate_vals.reshape(-1)[:, None]
        picked = jnp.where(keep[:, None], picked, 0.0)
        y = jnp.zeros((G, D), jnp.float32).at[src_tok].add(picked)
        y = jax.lax.psum(y, "model")
        return y.astype(tok.dtype), aux

    return f


def _moe_group_smap(expert_w, router, tok, cfg: ArchConfig):
    from jax.sharding import PartitionSpec as P

    from repro.parallel.jax_compat import (get_abstract_mesh,
                                           mesh_axis_sizes, shard_map)
    mesh = get_abstract_mesh()
    axes = mesh_axis_sizes(mesh)
    n_model = axes.get("model", 1)
    batch_axes = tuple(a for a in ("pod", "data") if a in axes)
    f = _moe_group_smap_fn(cfg, n_model, batch_axes)
    tok_spec = P(batch_axes if batch_axes else None)
    return shard_map(
        f, mesh=mesh,
        in_specs=(tok_spec, P(), P("model"), P("model"), P("model")),
        out_specs=(tok_spec, P()),
        check=False,
    )(tok, router, *expert_w)


def moe_shard_map_applicable(cfg: ArchConfig) -> bool:
    from repro.parallel.jax_compat import get_abstract_mesh, mesh_axis_sizes
    mesh = get_abstract_mesh()
    if mesh is None:
        return False
    axes = mesh_axis_sizes(mesh)
    n_model = axes.get("model", 1)
    return cfg.moe is not None and cfg.moe.n_experts % n_model == 0

def moe_specs(cfg: ArchConfig) -> dict:
    mo = cfg.moe
    D, E, Fe = cfg.d_model, mo.n_experts, mo.d_ff_expert
    s = {
        "ln": spec((D,), ("embed",), init="ones"),
        "router": spec((D, E), ("embed", "experts"), dtype=jnp.float32),
        "wg": spec((E, D, Fe), ("experts", "embed", "expert_mlp")),
        "wu": spec((E, D, Fe), ("experts", "embed", "expert_mlp")),
        "wd": spec((E, Fe, D), ("experts", "expert_mlp", "embed")),
    }
    if mo.n_shared_experts:
        Fs = mo.n_shared_experts * Fe
        s["sh_wg"] = spec((D, Fs), ("embed", "mlp"))
        s["sh_wu"] = spec((D, Fs), ("embed", "mlp"))
        s["sh_wd"] = spec((Fs, D), ("mlp", "embed"))
    return s


def _moe_group(p, tok, cfg: ArchConfig):
    """Dispatch one token group through the experts.

    tok: (G, D). Sort-free GShard-style dispatch: rank each (token, slot)
    within its expert by a one-hot cumsum, slot into per-expert capacity
    buffers, batched expert matmul, weighted scatter-add back. (An argsort
    dispatch lowers to XLA sort loops — whiles over the full buffer per
    pass — which wrecks both compile-time and the HBM roofline term.)
    Aux = Switch-style load-balance loss.
    """
    mo = cfg.moe
    G, D = tok.shape
    E, K = mo.n_experts, mo.top_k
    C = max(8, int(math.ceil(G * K * mo.capacity_factor / E / 8.0)) * 8)

    tok = constrain(tok, "batch", None)
    logits = (tok.astype(jnp.float32) @ p["router"])  # (G, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, topk_idx = jax.lax.top_k(probs, K)  # (G, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # load-balance aux: mean prob per expert x fraction of tokens routed
    me = probs.mean(axis=0)                                   # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[topk_idx.reshape(-1)].add(
        1.0 / (G * K))
    aux = E * jnp.sum(me * ce)

    flat_e = topk_idx.reshape(-1)                             # (G*K,)
    sel = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # (G*K, E)
    ranks = jnp.cumsum(sel, axis=0) - sel                     # rank in expert
    pos = jnp.sum(ranks * sel, axis=-1)                       # (G*K,)
    keep = pos < C
    pos_c = jnp.where(keep, pos, C)                           # C = drop slot
    src_tok = jnp.arange(G * K) // K

    # expert-major 3D scatter (.add: kept destinations unique, drops land
    # in the never-read slot C; add-combine avoids XLA's last-writer
    # machinery). Keeping the expert dim explicit lets GSPMD partition the
    # scatter/gather along the expert-sharded buffer.
    buf = jnp.zeros((E, C + 1, D), tok.dtype)
    buf = constrain(buf, "experts", None, None)
    buf = buf.at[flat_e, pos_c].add(tok[src_tok])
    xin = buf[:, :C, :]
    xin = constrain(xin, "experts", None, None)
    a = act_fn(cfg.act)
    hmid = a(jnp.einsum("ecd,edf->ecf", xin, p["wg"])) * \
        jnp.einsum("ecd,edf->ecf", xin, p["wu"])
    hout = jnp.einsum("ecf,efd->ecd", hmid, p["wd"])          # (E, C, D)
    hout = constrain(hout, "experts", None, None)

    hpad = jnp.pad(hout, ((0, 0), (0, 1), (0, 0)))
    picked = hpad[flat_e, pos_c].astype(jnp.float32) \
        * (gate_vals.reshape(-1))[:, None]
    picked = jnp.where(keep[:, None], picked, 0.0)
    y = jnp.zeros((G, D), jnp.float32).at[src_tok].add(picked)
    return y.astype(tok.dtype), aux


def moe_fwd(p, x, cfg: ArchConfig):
    """x: (B, S, D) -> (y, aux_loss).

    Token groups are SEQUENCE chunks (full batch dim per group), so the
    batch sharding survives the grouping reshape and the group scan's
    saved residuals stay sharded — grouping flat token blocks instead
    replicates the whole token tensor per device.
    """
    mo = cfg.moe
    B, S, D = x.shape
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    T = B * S
    G = min(mo.group_size, T)
    gs = max(1, G // B)       # sequence chunk per group
    if S % gs != 0:
        gs = 1
    nc = S // gs

    use_smap = MOE_SHARD_MAP["enabled"] and moe_shard_map_applicable(cfg)
    if use_smap:
        from jax.sharding import PartitionSpec as P
        # gather expert weights over the FSDP axis ONCE, outside the scan
        expert_w = tuple(jax.lax.with_sharding_constraint(
            p[k], P("model")) for k in ("wg", "wu", "wd"))
        router = jax.lax.with_sharding_constraint(
            p["router"].astype(jnp.float32), P())
        grp = lambda t: _moe_group_smap(expert_w, router, t, cfg)
    else:
        grp = lambda t: _moe_group(p, t, cfg)

    if nc == 1:
        y, aux = grp(h.reshape(T, D))
        y = y.reshape(B, S, D)
    else:
        tok = h.reshape(B, nc, gs, D).transpose(1, 0, 2, 3)

        def body(_, t):  # t: (B, gs, D)
            yg, auxg = grp(t.reshape(B * gs, D))
            return None, (yg.reshape(B, gs, D), auxg)

        _, (y, auxs) = jax.lax.scan(jax.checkpoint(body), None, tok)
        aux = auxs.mean()
        y = y.transpose(1, 0, 2, 3).reshape(B, S, D)

    if mo.n_shared_experts:
        a = act_fn(cfg.act)
        y = y + (a(h @ p["sh_wg"]) * (h @ p["sh_wu"])) @ p["sh_wd"]
    return constrain(y, "batch", "seq", "embed"), aux


# ==========================================================================
# SSD (Mamba-2 state-space duality)
# ==========================================================================

def ssd_specs(cfg: ArchConfig) -> dict:
    ss = cfg.ssm
    D = cfg.d_model
    di = ss.d_inner(D)
    nh = ss.heads(D)
    GN = ss.n_groups * ss.d_state
    w = ss.conv_width

    def a_init(key, shape, dtype):
        lo, hi = 1.0, 16.0
        u = jax.random.uniform(key, shape, jnp.float32)
        return jnp.log(lo + u * (hi - lo)).astype(dtype)

    return {
        "ln": spec((D,), ("embed",), init="ones"),
        "in_x": spec((D, di), ("embed", "ssm_inner")),
        "in_z": spec((D, di), ("embed", "ssm_inner")),
        "in_B": spec((D, GN), ("embed", None)),
        "in_C": spec((D, GN), ("embed", None)),
        "in_dt": spec((D, nh), ("embed", None)),
        "conv_x": spec((w, di), (None, "ssm_inner"), init="small_normal",
                       init_scale=0.5),
        "conv_B": spec((w, GN), (None, None), init="small_normal",
                       init_scale=0.5),
        "conv_C": spec((w, GN), (None, None), init="small_normal",
                       init_scale=0.5),
        "conv_b": spec((di + 2 * GN,), (None,), init="zeros"),
        "dt_bias": spec((nh,), (None,), init="zeros"),
        "A_log": spec((nh,), (None,), custom_init=a_init),
        "D_skip": spec((nh,), (None,), init="ones"),
        "gnorm": spec((di,), ("ssm_inner",), init="ones"),
        "out_proj": spec((di, D), ("ssm_inner", "embed")),
    }


def _causal_conv(x, w, b):
    """x: (B, S, C); w: (W, C) depthwise causal conv via shifted adds."""
    W = w.shape[0]
    y = x * w[W - 1]
    for i in range(1, W):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :-i or None, :]
        y = y + shifted * w[W - 1 - i]
    return y + b


def _ssd_chunk_scan(xh, dt, A, Bm, Cm, h0=None):
    """Chunked SSD core.

    xh: (B, S, nh, hd); dt: (B, S, nh) (post-softplus); A: (nh,) negative;
    Bm/Cm: (B, S, nh, N) (already broadcast from groups to heads).
    Returns y: (B, S, nh, hd) and the final state (B, nh, hd, N).
    """
    Bsz, S, nh, hd = xh.shape
    N = Bm.shape[-1]
    Q = min(S, 256) if S % 256 == 0 or S < 256 else _largest_chunk(S)
    nc = S // Q

    def split(t):
        return t.reshape(Bsz, nc, Q, *t.shape[2:]).transpose(
            1, 0, *range(2, t.ndim + 1))

    xc, dtc, Bc, Cc = split(xh), split(dt), split(Bm), split(Cm)
    if h0 is None:
        h0 = jnp.zeros((Bsz, nh, hd, N), jnp.float32)

    def body(h, xs):
        xq, dtq, Bq, Cq = xs  # (B,Q,nh,·)
        dA = dtq.astype(jnp.float32) * A  # (B,Q,nh) negative
        cum = jnp.cumsum(dA, axis=1)      # within-chunk decay logs
        # intra-chunk (dual quadratic form). Mask the log BEFORE exp —
        # non-causal entries have positive logs that overflow exp and
        # poison the backward pass (inf * 0 = NaN) if masked after.
        Lmat = cum[:, :, None, :] - cum[:, None, :, :]  # (B,Qi,Qj,nh)
        iq = jnp.arange(Q)
        causal = iq[:, None] >= iq[None, :]
        Lmat = jnp.where(causal[None, :, :, None], Lmat, -1e30)
        Lmat = jnp.exp(Lmat)
        scores = jnp.einsum("bihn,bjhn->bijh", Cq.astype(jnp.float32),
                            Bq.astype(jnp.float32))
        dx = dtq.astype(jnp.float32)[..., None] * xh_f(xq)  # (B,Q,nh,hd)
        y_intra = jnp.einsum("bijh,bijh,bjhp->bihp",
                             scores, Lmat, dx)
        # inter-chunk: contribution of carried state
        y_inter = jnp.einsum("bihn,bhpn,bih->bihp",
                             Cq.astype(jnp.float32), h, jnp.exp(cum))
        # state update
        decay_to_end = jnp.exp(cum[:, -1:, :] - cum)  # (B,Q,nh)
        s_chunk = jnp.einsum("bjhn,bjhp,bjh->bhpn",
                             Bq.astype(jnp.float32), dx, decay_to_end)
        h_new = h * jnp.exp(cum[:, -1, :])[:, :, None, None] + s_chunk
        return h_new, (y_intra + y_inter)

    h_final, yc = jax.lax.scan(jax.checkpoint(body), h0,
                               (xc, dtc, Bc, Cc))
    y = yc.transpose(1, 0, 2, 3, 4).reshape(Bsz, S, nh, hd)
    return y, h_final


def xh_f(x):
    return x.astype(jnp.float32)


def _largest_chunk(S: int) -> int:
    for q in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if S % q == 0:
            return q
    return 1


def _ssd_inputs(p, h, cfg: ArchConfig, conv_state=None):
    """Shared projection + causal conv for fwd and decode.

    h: (B, S, D). Returns x (B,S,nh,hd), z, dt, Bm, Cm (+ new conv tail).
    """
    ss = cfg.ssm
    D = cfg.d_model
    di, nh = ss.d_inner(D), ss.heads(D)
    GN = ss.n_groups * ss.d_state
    B_, S, _ = h.shape

    x = h @ p["in_x"]
    z = h @ p["in_z"]
    Bm = h @ p["in_B"]
    Cm = h @ p["in_C"]
    dt = h @ p["in_dt"]

    bx, bB, bC = jnp.split(p["conv_b"], [di, di + GN])
    if conv_state is not None:  # decode: prepend stored tail
        tail_x, tail_B, tail_C = conv_state
        x_full = jnp.concatenate([tail_x, x], axis=1)
        B_full = jnp.concatenate([tail_B, Bm], axis=1)
        C_full = jnp.concatenate([tail_C, Cm], axis=1)
        W = ss.conv_width
        x = _causal_conv(x_full, p["conv_x"], bx)[:, W - 1:]
        Bm = _causal_conv(B_full, p["conv_B"], bB)[:, W - 1:]
        Cm = _causal_conv(C_full, p["conv_C"], bC)[:, W - 1:]
        new_state = (x_full[:, -(W - 1):], B_full[:, -(W - 1):],
                     C_full[:, -(W - 1):])
    else:
        x_pre, B_pre, C_pre = x, Bm, Cm
        x = _causal_conv(x, p["conv_x"], bx)
        Bm = _causal_conv(Bm, p["conv_B"], bB)
        Cm = _causal_conv(Cm, p["conv_C"], bC)
        W = ss.conv_width
        new_state = (x_pre[:, -(W - 1):], B_pre[:, -(W - 1):],
                     C_pre[:, -(W - 1):])
    x = jax.nn.silu(x)
    Bm = jax.nn.silu(Bm)
    Cm = jax.nn.silu(Cm)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])

    x = x.reshape(B_, S, nh, ss.head_dim)
    # broadcast groups -> heads
    g_of_h = nh // ss.n_groups
    Bm = jnp.repeat(Bm.reshape(B_, S, ss.n_groups, ss.d_state), g_of_h,
                    axis=2)
    Cm = jnp.repeat(Cm.reshape(B_, S, ss.n_groups, ss.d_state), g_of_h,
                    axis=2)
    return x, z, dt, Bm, Cm, new_state


def _ssd_output(p, y, x, z, cfg: ArchConfig):
    ss = cfg.ssm
    B_, S = y.shape[0], y.shape[1]
    di = ss.d_inner(cfg.d_model)
    y = y + x.astype(jnp.float32) * p["D_skip"][..., None]
    y = y.reshape(B_, S, di)
    y = rms_norm(y.astype(z.dtype) * jax.nn.silu(z), p["gnorm"],
                 cfg.norm_eps)
    out = y @ p["out_proj"]
    return constrain(out, "batch", "seq", "embed")


def ssd_fwd(p, x_res, cfg: ArchConfig, *, return_cache: bool = False):
    """Mamba-2 block over a sequence. x_res: (B, S, D)."""
    h = rms_norm(x_res, p["ln"], cfg.norm_eps)
    x, z, dt, Bm, Cm, conv_tail = _ssd_inputs(p, h, cfg)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, h_final = _ssd_chunk_scan(x, dt, A, Bm, Cm)
    out = _ssd_output(p, y, x, z, cfg)
    if return_cache:
        return out, (conv_tail, h_final)
    return out


def ssd_decode(p, x_res, conv_state, ssm_state, cfg: ArchConfig):
    """Single-token recurrent update. conv_state: 3x (B, W-1, ·);
    ssm_state: (B, nh, hd, N)."""
    h = rms_norm(x_res, p["ln"], cfg.norm_eps)
    x, z, dt, Bm, Cm, new_conv = _ssd_inputs(p, h, cfg, conv_state)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    # recurrent step: h' = exp(dt*A) h + dt * B (outer) x ; y = C . h'
    dtq = dt[:, 0]                     # (B, nh)
    xq = x[:, 0].astype(jnp.float32)   # (B, nh, hd)
    Bq = Bm[:, 0].astype(jnp.float32)  # (B, nh, N)
    Cq = Cm[:, 0].astype(jnp.float32)
    decay = jnp.exp(dtq * A)[..., None, None]
    upd = jnp.einsum("bh,bhp,bhn->bhpn", dtq, xq, Bq)
    ssm_new = ssm_state * decay + upd
    y = jnp.einsum("bhpn,bhn->bhp", ssm_new, Cq)[:, None]  # (B,1,nh,hd)
    out = _ssd_output(p, y, x, z, cfg)
    return out, new_conv, ssm_new


# ==========================================================================
# Hybrid (hymba): parallel attention + SSD branches sharing the residual
# ==========================================================================

def hybrid_specs(cfg: ArchConfig) -> dict:
    return {"attn": attn_specs(cfg), "ssd": ssd_specs(cfg)}


def hybrid_fwd(p, x, cfg: ArchConfig, *, window: Window = None,
               return_cache: bool = False):
    if return_cache:
        a, kv = attn_fwd(p["attn"], x, cfg, window=window, return_cache=True)
        s, st = ssd_fwd(p["ssd"], x, cfg, return_cache=True)
        return 0.5 * (a + s), (kv, st)
    a = attn_fwd(p["attn"], x, cfg, window=window)
    s = ssd_fwd(p["ssd"], x, cfg)
    return 0.5 * (a + s)


def hybrid_decode(p, x, k_cache, v_cache, conv_state, ssm_state, cache_len,
                  cfg: ArchConfig, *, window: Window = None):
    a, k_cache, v_cache = attn_decode(p["attn"], x, k_cache, v_cache,
                                      cache_len, cfg, window=window)
    s, conv_state, ssm_state = ssd_decode(p["ssd"], x, conv_state, ssm_state,
                                          cfg)
    return 0.5 * (a + s), k_cache, v_cache, conv_state, ssm_state
