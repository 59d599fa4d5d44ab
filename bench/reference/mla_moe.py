"""Operator traces of latent-attention and mixture-of-experts models,
restated from DeepSeek-V2 (arXiv:2405.04434).

Covers attention-family models with either attention (grouped-query
heads, or multi-head latent attention, §2.1) and DeepSeekMoE (§2.2)
after their leading dense SwiGLU layers. Prefill
and train expand the latent into per-head keys and values; decode
absorbs the key and value up-projection into the query and the output,
so every head reads the one latent cache of ``kv_lora + rope`` values a
position. Routed experts are expert parallel over
``gcd(n_experts, n_chips)`` chips; routing is balanced and drops no
token, so a chip's experts take as many token slots as its tokens send
out, as evenly as integers allow. Conventions (bf16 tensors, the SRAM
tile rule, the VU post-processing each GEMM output, the training step's
3x compute and its gradient all-reduce and Adam) are those of
``opgen.py``'s dense and SSM blocks, whose helpers build each op.
"""
from __future__ import annotations

import math

from bench.reference.opgen import (BF16, F32, _op, collective, matmul,
                                   vector, vocab_padded)


def per_head_matmul(name, T, H, K, N) -> dict:
    """``H`` heads' own [T,K]x[K,N] products, batched as one GEMM of
    M = T x H: every head's (K, N) weight block streams once."""
    M = T * H
    w = H * K * N * BF16
    b = w + M * K * BF16 + M * N * BF16
    if M >= 512:
        tile = min(int(0.75 * 128 * 2 ** 20),
                   w + 2 * 512 * K * BF16 + 512 * N * F32)
    else:
        tile = min(8 << 20, b)
    return _op(name, flops_sa=2.0 * M * K * N, flops_vu=M * N * 2.0,
               bytes_hbm=float(b), sram_demand=int(tile), mm=(M, K, N))


def swiglu(name, elems, count=1) -> dict:
    return dict(vector(name, elems, flops_per_elem=3, bytes_per_elem=0.5),
                count=count)


def gqa(arch, T, kv_len, decode, tp) -> list[dict]:
    D = arch["d_model"]
    H, Hkv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    ops = [matmul("qkv", T, D, (H + 2 * Hkv) * hd // tp)]
    if decode:
        ops.append(_op(
            "attn_decode", flops_vu=2.0 * T * kv_len * hd * 2 * H / tp,
            bytes_hbm=kv_len * Hkv * hd * BF16 * 2 * max(1, T // 8) / tp,
            sram_demand=8 << 20))
    else:
        ops.append(_op(
            "attention", flops_sa=2.0 * T * kv_len * hd * 2 * H / tp,
            bytes_hbm=3 * T * D * BF16 / tp, mm=(T, hd, kv_len),
            sram_demand=24 << 20))
    ops.append(matmul("out_proj", T, H * hd // tp, D))
    return ops


def mla(arch, T, kv_len, decode, tp) -> list[dict]:
    """Latent attention of ``T`` tokens over this chip's heads."""
    m, D = arch["mla"], arch["d_model"]
    H = max(1, arch["n_heads"] // tp)
    nope, rope, v = m["nope_head_dim"], m["rope_head_dim"], m["v_head_dim"]
    c_q, c_kv = m["q_lora_rank"], m["kv_lora_rank"]
    ops = [matmul("q_a", T, D, c_q),                  # W^DQ
           matmul("kv_a", T, D, c_kv + rope),         # W^DKV and W^KR
           # RMS norms of the two latents, rope on q's and k's rope dims
           vector("mla_norm_rope", T * (c_q + c_kv + (H + 1) * rope),
                  flops_per_elem=4),
           matmul("q_b", T, c_q, H * (nope + rope))]  # W^UQ and W^QR
    if decode:
        # q_nope W^UK per head; scores against the latent cache, then the
        # values' latent sum, one sequence at a time; W^UV per head
        ops += [per_head_matmul("q_absorb", T, H, nope, c_kv),
                _op("mla_decode",
                    flops_sa=(2.0 * H * kv_len * (c_kv + rope)
                              + 2.0 * H * kv_len * c_kv),
                    bytes_hbm=kv_len * (c_kv + rope) * BF16,
                    mm=(H, c_kv + rope, kv_len), sram_demand=8 << 20,
                    count=T),
                per_head_matmul("o_absorb", T, H, c_kv, v)]
    else:
        ops += [matmul("kv_b", T, c_kv, H * (nope + v)),  # W^UK and W^UV
                _op("attention",
                    flops_sa=2.0 * T * kv_len * H * ((nope + rope) + v),
                    bytes_hbm=T * H * (2 * (nope + rope) + v) * BF16,
                    mm=(T, nope + rope, kv_len), sram_demand=24 << 20)]
    ops.append(matmul("o_proj", T, H * v, D))         # W^O
    return ops


def routed(R, E, D, F) -> list[dict]:
    """``R`` token slots over this chip's ``E`` experts: the first
    ``R mod n`` of the ``n = min(E, R)`` busy experts take one more."""
    n = min(E, R)
    sizes = [R // n + (1 if e < R % n else 0) for e in range(n)]
    ops = []
    for rows in sorted(set(sizes), reverse=True):
        k = sizes.count(rows)
        ops += [dict(matmul("expert_up", rows, D, 2 * F), count=k),
                swiglu("expert_swiglu", rows * F, count=k),
                dict(matmul("expert_down", rows, F, D), count=k)]
    return ops


def deepseek_moe(arch, T, n_chips, tp) -> list[dict]:
    mo, D = arch["moe"], arch["d_model"]
    E_all, k, F = mo["n_experts"], mo["top_k"], mo["d_ff_expert"]
    ep = math.gcd(E_all, n_chips)
    T_m = max(1, T // tp)               # tokens this chip routes
    R = T_m * k                         # slots its experts receive
    a2a = R * D * BF16 * (ep - 1) / ep  # each leaves (ep-1)/ep of the time
    ops = [matmul("router", T_m, D, E_all),
           vector("router_topk", T_m * E_all, flops_per_elem=4),
           collective("a2a_dispatch", a2a)]
    ops += routed(R, E_all // ep, D, F)
    ops.append(collective("a2a_combine", a2a))
    if mo["n_shared_experts"]:
        fs = mo["n_shared_experts"] * F
        ops += [matmul("shared_up", T, D, 2 * fs // tp),
                swiglu("shared_swiglu", T * fs / tp),
                matmul("shared_down", T, fs // tp, D)]
    return ops


def n_params(arch: dict) -> int:
    """Parameters of an attention-family model with latent or
    grouped-query attention and DeepSeekMoE after its leading dense
    layers, counted from its published layout."""
    D, V, L = arch["d_model"], vocab_padded(arch), arch["n_layers"]
    total = V * D + D                         # embedding, final norm
    if not arch["tie_embeddings"]:
        total += D * V                        # output head
    H = arch["n_heads"]
    m = arch.get("mla")
    if m:
        qk = m["nope_head_dim"] + m["rope_head_dim"]
        attn = (D                             # attention norm
                + D * m["q_lora_rank"] + m["q_lora_rank"]  # W^DQ, norm
                + m["q_lora_rank"] * H * qk                # W^UQ, W^QR
                + D * (m["kv_lora_rank"] + m["rope_head_dim"])
                + m["kv_lora_rank"]                        # its norm
                + m["kv_lora_rank"] * H * (m["nope_head_dim"]
                                           + m["v_head_dim"])
                + H * m["v_head_dim"] * D)                 # W^O
    else:
        Hkv, hd = arch["n_kv_heads"], arch["head_dim"]
        attn = D + 2 * D * H * hd + 2 * D * Hkv * hd
        if arch.get("qkv_bias"):
            attn += H * hd + 2 * Hkv * hd
        if arch.get("qk_norm"):
            attn += 2 * hd
    mo = arch["moe"]
    lead = mo["first_dense_layers"]
    dense = D + 3 * D * arch["d_ff"]          # norm, gate, up, down
    moe = (D + D * mo["n_experts"]            # norm, router
           + (mo["n_experts"] + mo["n_shared_experts"])
           * 3 * D * mo["d_ff_expert"])
    return total + L * attn + lead * dense + (L - lead) * moe


def trace(arch: dict, kind: str, seq_len: int, global_batch: int,
          n_chips: int, tp: int) -> list[dict]:
    """The per-chip operator trace of one step of ``kind`` of a model
    with DeepSeekMoE, laid out as ``opgen.trace`` lays out dense
    blocks."""
    decode, train = kind == "decode", kind == "train"
    dp = max(1, n_chips // tp)
    T = max(1, (global_batch if decode else global_batch * seq_len) // dp)
    D = arch["d_model"]
    attend = mla if arch.get("mla") else gqa
    attn = attend(arch, T, seq_len, decode, tp)
    tail = []
    if tp > 1:
        tail.append(collective("ar_layer", 2 * T * D * BF16 * (tp - 1) / tp))
    tail.append(vector("norms", T * D, flops_per_elem=8))
    dense = [matmul("mlp_up", T, D, 2 * arch["d_ff"] // tp),
             matmul("mlp_down", T, arch["d_ff"] // tp, D)
             ] if arch["d_ff"] else []
    lead = arch["moe"]["first_dense_layers"]
    moe = deepseek_moe(arch, T, n_chips, tp)
    layers = ([attn + dense + tail] * lead
              + [attn + moe + tail] * (arch["n_layers"] - lead))
    mult = 3 if train else 1                  # forward + 2x backward
    ops = [dict(o, flops_sa=o["flops_sa"] * mult,
                flops_vu=o["flops_vu"] * mult,
                bytes_hbm=o["bytes_hbm"] * mult)
           for layer in layers for o in layer]
    ops.append(matmul("lm_head", T, D, vocab_padded(arch) // tp))
    if train:
        p = n_params(arch)
        ops.append(collective("grad_allreduce", 2 * p * BF16 / (tp * dp)))
        ops.append(vector("adam", p / (tp * dp), flops_per_elem=12,
                          bytes_per_elem=16))
    return ops
