"""Model assembly: embedding -> scanned layer stack -> unembedding.

One implementation serves all ten assigned architectures; family-specific
behaviour (SSD, MoE, MLA, hybrid windows, encoder-only, modality frontends)
is dispatched from the ArchConfig. Layers run under ``jax.lax.scan`` with
per-layer remat, so HLO size and compile time are O(1) in depth and the
roofline extractor multiplies while-body costs by the trip count.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import blocks, registry
from repro.models.common import rms_norm
from repro.models.param import cast_tree
from repro.parallel.sharding import constrain

REMAT_POLICIES = {
    "full": None,  # save nothing
    "dots": "dots_with_no_batch_dims_saveable",
    "none": "everything_saveable",
}


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    pol = None
    if policy == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint(fn, policy=pol)


# --------------------------------------------------------------------------
# Embedding / unembedding
# --------------------------------------------------------------------------

def embed_inputs(params, batch: dict, cfg: ArchConfig, dtype=jnp.bfloat16):
    """Returns x: (B, S_total, D) and the prefix length (vlm image tokens)."""
    emb = params["embed"].astype(dtype)
    prefix = 0
    if cfg.frontend == "audio":
        x = batch["frames"].astype(dtype) @ params["frontend_proj"].astype(
            dtype)
        # sinusoidal positions (conv-positional frontend is stubbed)
        S, D = x.shape[1], x.shape[2]
        pos = jnp.arange(S)[:, None].astype(jnp.float32)
        div = jnp.exp(jnp.arange(0, D, 2, dtype=jnp.float32)
                      * (-jnp.log(10000.0) / D))
        pe = jnp.zeros((S, D), jnp.float32)
        pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
        pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
        x = x + pe.astype(dtype)
    elif cfg.frontend == "vision":
        img = batch["patches"].astype(dtype) @ params["frontend_proj"].astype(
            dtype)
        tx = emb[batch["tokens"]]
        tx = tx * jnp.asarray(cfg.d_model ** 0.5, dtype)  # gemma scaling
        x = jnp.concatenate([img, tx], axis=1)
        prefix = cfg.frontend_seq
    else:
        x = emb[batch["tokens"]]
    return constrain(x, "batch", "seq", "embed"), prefix


def unembed(params, x, cfg: ArchConfig):
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if "lm_head" in params:
        logits = h @ params["lm_head"].astype(h.dtype)
    else:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(h.dtype))
    return constrain(logits, "batch", "seq", "vocab")


# --------------------------------------------------------------------------
# Layer application (shared by forward and prefill)
# --------------------------------------------------------------------------

def _apply_layer(p, x, cfg: ArchConfig, *, window, prefix_len: int,
                 prefill: bool):
    """Returns (x, aux, cache_entry_or_None)."""
    fam = cfg.family
    aux = jnp.zeros((), jnp.float32)
    cache = None
    if fam == "ssm":
        if prefill:
            y, cache = blocks.ssd_fwd(p["ssd"], x, cfg, return_cache=True)
        else:
            y = blocks.ssd_fwd(p["ssd"], x, cfg)
        return x + y, aux, cache
    if fam == "hybrid":
        if prefill:
            y, cache = blocks.hybrid_fwd(p["mix"], x, cfg, window=window,
                                         return_cache=True)
        else:
            y = blocks.hybrid_fwd(p["mix"], x, cfg, window=window)
        x = x + y
        x = x + blocks.mlp_fwd(p["mlp"], x, cfg)
        return x, aux, cache
    # attention (GQA or MLA)
    if cfg.mla:
        if prefill:
            y, cache = blocks.mla_fwd(p["attn"], x, cfg, return_cache=True)
        else:
            y = blocks.mla_fwd(p["attn"], x, cfg)
    else:
        if prefill:
            y, cache = blocks.attn_fwd(p["attn"], x, cfg, window=window,
                                       prefix_len=prefix_len,
                                       return_cache=True)
        else:
            y = blocks.attn_fwd(p["attn"], x, cfg, window=window,
                                prefix_len=prefix_len)
    x = x + y
    if "moe" in p:
        y, aux = blocks.moe_fwd(p["moe"], x, cfg)
        x = x + y
    else:
        x = x + blocks.mlp_fwd(p["mlp"], x, cfg)
    return x, aux, cache


def _apply_dense0(p, x, cfg: ArchConfig, *, prefill: bool):
    """DeepSeek leading dense layer: MLA attn + wide dense MLP."""
    if prefill:
        y, cache = blocks.mla_fwd(p["attn"], x, cfg, return_cache=True)
    else:
        y = blocks.mla_fwd(p["attn"], x, cfg)
        cache = None
    x = x + y
    x = x + blocks.mlp_fwd(p["mlp"], x, cfg)
    return x, cache


# --------------------------------------------------------------------------
# Sequence forward (train / prefill)
# --------------------------------------------------------------------------

# §Perf hillclimb toggle: run sliding-window archs as static-window layer
# SEGMENTS (scan per contiguous SWA run, global layers unrolled) so the
# triangle/window-blocked attention kernel can skip dead kv blocks.
STATIC_WINDOW_SEGMENTS = {"enabled": False}


def _segmented_stack(params, x, cfg, *, prefix_len, prefill, remat, dtype):
    """hymba-style stack as [SWA segment]* with global layers unrolled."""
    L = cfg.n_layers
    glob = sorted(registry.global_layer_indices(cfg))
    layers = jax.tree.map(lambda a: a.astype(dtype)
                          if jnp.issubdtype(a.dtype, jnp.floating) else a,
                          params["layers"])
    aux = jnp.zeros((), jnp.float32)
    caches = []

    def seg_scan(x, aux, lo, hi, window):
        seg = jax.tree.map(lambda a: a[lo:hi], layers)

        def body(carry, p_layer):
            x, aux = carry
            x, a2, cache = _apply_layer(p_layer, x, cfg, window=window,
                                        prefix_len=prefix_len,
                                        prefill=prefill)
            return (x, aux + a2), cache

        (x, aux), c = jax.lax.scan(_remat(body, remat), (x, aux), seg)
        return x, aux, c

    pos = 0
    bounds = glob + [L]
    for g in bounds:
        if g > pos:  # SWA segment [pos, g)
            x, aux, c = seg_scan(x, aux, pos, g, cfg.sliding_window)
            caches.append(c)
        if g < L:    # the global layer g, unrolled, full attention
            pl = jax.tree.map(lambda a: a[g], layers)

            def one(pl, x):
                return _apply_layer(pl, x, cfg, window=None,
                                    prefix_len=prefix_len, prefill=prefill)

            x, a2, c = _remat(one, remat)(pl, x)
            aux = aux + a2
            if c is not None:
                caches.append(jax.tree.map(lambda t: t[None], c))
        pos = g + 1
    if prefill:
        cache = jax.tree.map(lambda *cs: jnp.concatenate(cs, axis=0),
                             *caches)
    else:
        cache = None
    return x, aux, cache


def forward(params, batch: dict, cfg: ArchConfig, *, prefill: bool = False,
            remat: str = "full", dtype=jnp.bfloat16):
    """Full-sequence forward.

    Returns (logits, aux_loss) when ``prefill=False``;
    (last_logits, cache) when ``prefill=True``.
    """
    params = cast_tree(params, dtype)
    x, prefix_len = embed_inputs(params, batch, cfg, dtype)
    S = x.shape[1]
    warr = registry.window_array(cfg, S)

    def body(carry, xs):
        x, aux = carry
        if warr is not None:
            p_layer, w = xs
        else:
            p_layer, w = xs, None
        x, aux2, cache = _apply_layer(
            p_layer, x, cfg, window=w, prefix_len=prefix_len,
            prefill=prefill)
        return (x, aux + aux2), cache

    if "dense0" in params:
        x, cache0 = _apply_dense0(params["dense0"], x, cfg, prefill=prefill)
    else:
        cache0 = None

    if warr is not None and STATIC_WINDOW_SEGMENTS["enabled"]:
        x, aux, caches = _segmented_stack(
            params, x, cfg, prefix_len=prefix_len, prefill=prefill,
            remat=remat, dtype=dtype)
        if prefill:
            last = unembed(params, x[:, -1:], cfg)
            return last, {"layers": caches}
        return unembed(params, x, cfg), aux

    layers = jax.tree.map(lambda a: a.astype(dtype)
                          if jnp.issubdtype(a.dtype, jnp.floating) else a,
                          params["layers"])
    xs = (layers, warr) if warr is not None else layers
    (x, aux), caches = jax.lax.scan(
        _remat(body, remat), (x, jnp.zeros((), jnp.float32)), xs)

    if prefill:
        last = unembed(params, x[:, -1:], cfg)
        full_cache = {"layers": caches}
        if cache0 is not None:
            full_cache["dense0"] = cache0
        return last, full_cache
    logits = unembed(params, x, cfg)
    return logits, aux


# --------------------------------------------------------------------------
# Loss
# --------------------------------------------------------------------------

def loss_fn(params, batch: dict, cfg: ArchConfig, *, remat: str = "full",
            dtype=jnp.bfloat16, aux_weight: float = 0.01,
            z_weight: float = 1e-4):
    logits, aux = forward(params, batch, cfg, prefill=False, remat=remat,
                          dtype=dtype)
    labels = batch["labels"]
    if cfg.frontend == "vision":  # image positions carry no labels
        logits = logits[:, cfg.frontend_seq:]
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = (lse - ll).mean()
    z = (lse ** 2).mean()  # z-loss keeps logits bounded
    loss = ce + aux_weight * aux + z_weight * z
    metrics = {"loss": loss, "ce": ce, "aux": aux, "z": z}
    return loss, metrics


# --------------------------------------------------------------------------
# Decode (one token against the cache)
# --------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> dict:
    """Abstract-shape-compatible cache initializer (also used by input_specs).
    """
    L = registry.n_scanned_layers(cfg)
    c: dict[str, Any] = {}
    entry = layer_cache_struct(cfg, batch, max_seq, dtype)
    c["layers"] = jax.tree.map(
        lambda s: jnp.zeros((L, *s.shape), s.dtype), entry)
    if cfg.moe and cfg.moe.first_dense_layers:
        d0 = mla_cache_struct(cfg, batch, max_seq, dtype)
        c["dense0"] = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), d0)
    return c


def mla_cache_struct(cfg, B, S, dtype):
    m = cfg.mla
    return (jax.ShapeDtypeStruct((B, S, m.kv_lora_rank), dtype),
            jax.ShapeDtypeStruct((B, S, m.rope_head_dim), dtype))


def ssd_cache_struct(cfg, B, dtype):
    ss = cfg.ssm
    di = ss.d_inner(cfg.d_model)
    nh = ss.heads(cfg.d_model)
    GN = ss.n_groups * ss.d_state
    W = ss.conv_width
    conv = (jax.ShapeDtypeStruct((B, W - 1, di), dtype),
            jax.ShapeDtypeStruct((B, W - 1, GN), dtype),
            jax.ShapeDtypeStruct((B, W - 1, GN), dtype))
    ssm = jax.ShapeDtypeStruct((B, nh, ss.head_dim, ss.d_state), jnp.float32)
    return (conv, ssm)


def kv_cache_struct(cfg, B, S, dtype):
    return (jax.ShapeDtypeStruct((B, S, cfg.n_kv_heads, cfg.head_dim), dtype),
            jax.ShapeDtypeStruct((B, S, cfg.n_kv_heads, cfg.head_dim), dtype))


def layer_cache_struct(cfg: ArchConfig, B: int, S: int, dtype):
    fam = cfg.family
    if fam == "ssm":
        return ssd_cache_struct(cfg, B, dtype)
    if fam == "hybrid":
        return (kv_cache_struct(cfg, B, S, dtype),
                ssd_cache_struct(cfg, B, dtype))
    if cfg.mla:
        return mla_cache_struct(cfg, B, S, dtype)
    return kv_cache_struct(cfg, B, S, dtype)


def _decode_layer(p, x, cache, cache_len, cfg: ArchConfig, *, window,
                  prefix_len: int):
    fam = cfg.family
    if fam == "ssm":
        (conv, ssm) = cache
        y, conv, ssm = blocks.ssd_decode(p["ssd"], x, conv, ssm, cfg)
        return x + y, (conv, ssm)
    if fam == "hybrid":
        (k, v), (conv, ssm) = cache
        y, k, v, conv, ssm = blocks.hybrid_decode(
            p["mix"], x, k, v, conv, ssm, cache_len, cfg, window=window)
        x = x + y
        x = x + blocks.mlp_fwd(p["mlp"], x, cfg)
        return x, ((k, v), (conv, ssm))
    if cfg.mla:
        c, kr = cache
        y, c, kr = blocks.mla_decode(p["attn"], x, c, kr, cache_len, cfg)
        cache = (c, kr)
    else:
        k, v = cache
        y, k, v = blocks.attn_decode(p["attn"], x, k, v, cache_len, cfg,
                                     window=window, prefix_len=prefix_len)
        cache = (k, v)
    x = x + y
    if "moe" in p:
        y, _ = blocks.moe_fwd(p["moe"], x, cfg)
        x = x + y
    else:
        x = x + blocks.mlp_fwd(p["mlp"], x, cfg)
    return x, cache


def decode_step(params, cache: dict, batch: dict, cfg: ArchConfig, *,
                dtype=jnp.bfloat16):
    """One decode step. batch: {"tokens": (B,1) int32, "cache_len": ()}.

    Returns (logits (B,1,V), new_cache). For VLM archs the image prefix is
    assumed to live in cache slots [0, frontend_seq).
    """
    params = cast_tree(params, dtype)
    cache_len = batch["cache_len"]
    prefix_len = cfg.frontend_seq if cfg.frontend == "vision" else 0
    emb = params["embed"].astype(dtype)
    x = emb[batch["tokens"]]
    if cfg.frontend == "vision":
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    x = constrain(x, "batch", None, "embed")

    # decode positions: SSM states don't use positions; attention uses
    # cache_len as the rope position/causal boundary.
    seq_hint = 0
    for leaf in jax.tree.leaves(cache["layers"]):
        if leaf.ndim >= 3:
            seq_hint = max(seq_hint, leaf.shape[2] if leaf.ndim > 3
                           else leaf.shape[2])
    warr = registry.window_array(cfg, seq_hint)

    if "dense0" in cache:
        c, kr = cache["dense0"]
        y, c, kr = blocks.mla_decode(params["dense0"]["attn"], x, c, kr,
                                     cache_len, cfg)
        x = x + y
        x = x + blocks.mlp_fwd(params["dense0"]["mlp"], x, cfg)
        new_dense0 = (c, kr)
    else:
        new_dense0 = None

    def body(x, xs):
        if warr is not None:
            p_layer, cache_slice, w = xs
        else:
            (p_layer, cache_slice), w = xs, None
        x, new_slice = _decode_layer(p_layer, x, cache_slice, cache_len, cfg,
                                     window=w, prefix_len=prefix_len)
        return x, new_slice

    layers = jax.tree.map(lambda a: a.astype(dtype)
                          if jnp.issubdtype(a.dtype, jnp.floating) else a,
                          params["layers"])
    xs = ((layers, cache["layers"], warr) if warr is not None
          else (layers, cache["layers"]))
    x, new_layers = jax.lax.scan(body, x, xs)

    logits = unembed(params, x, cfg)
    new_cache = {"layers": new_layers}
    if new_dense0 is not None:
        new_cache["dense0"] = new_dense0
    return logits, new_cache


def prefill_step(params, batch: dict, cfg: ArchConfig, *,
                 remat: str = "full", dtype=jnp.bfloat16):
    """Prefill: build the KV/state cache for a prompt, return last logits."""
    return forward(params, batch, cfg, prefill=True, remat=remat,
                   dtype=dtype)
