"""Operator traces of hybrid Mamba-2 / attention / MoE models, restated
from Nemotron-H (arXiv:2504.03624; NVIDIA-Nemotron-3-Nano-30B-A3B's
config.json) and Mamba-2 (arXiv:2405.21060).

The model is a stack of single-mixer blocks in the published
``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``E`` a mixture of
experts with squared-ReLU (not gated) experts and one shared expert,
``*`` grouped-query attention. Each block ends in the layer tail: the
all-reduce over the tensor-parallel group where there is one, and the
norms. Routed experts are expert parallel over ``gcd(n_experts,
n_chips)`` chips with balanced routing and no token dropped, as in
``mla_moe.py``; conventions (bf16 tensors, the SRAM tile rule, the VU
post-processing each GEMM output, an activation fused with its GEMM's
output at half a byte an element) are those of ``opgen.py``, whose
helpers build each op.

Where this trace and ``opgen.py``'s dense and SSM blocks differ, this
one follows the published model:

* decode reads the KV cache of every sequence (the dense block prices
  ``max(1, T // 8)`` of them);
* a Mamba-2 decode step reads and writes each sequence's state of
  heads x head_dim x d_state, in bf16 (the SSM block moves none);
* the Mamba-2 input projection emits x, z, B, C and one dt a head, and
  the conv runs over x, B and C (the SSM block keeps x and z alone).

Departures from the published model, all shared with the other blocks
or changing no shape: no causal halving of attention or scan FLOPs; the
conv state's decode traffic, the gated RMSNorm over d_inner and the
attention's wider-than-hidden q in prefill activation bytes are not
counted; the sigmoid router's correction bias and scaling change no
shape. A train step is not traced.
"""
from __future__ import annotations

import math

from bench.reference.mla_moe import gqa
from bench.reference.opgen import (BF16, _op, collective, matmul, vector,
                                   vocab_padded)


def mamba2(arch, T, decode, tp) -> list[dict]:
    """One Mamba-2 mixer over ``T`` tokens, heads split ``tp`` ways."""
    s, D = arch["ssm"], arch["d_model"]
    nh, hd, N = s["n_heads"], s["head_dim"], s["d_state"]
    di = nh * hd
    bc = 2 * s["n_groups"] * N                 # B and C
    scan_bytes = T * di * BF16 * 3 / tp        # x in, y out, z
    if decode:                                  # the state, read and written
        scan_bytes += T * nh * hd * N * BF16 * 2 / tp
    return [
        matmul("in_proj", T, D, (2 * di + bc + nh) // tp),  # x z B C dt
        vector("conv+act", T * (di + bc) / tp, flops_per_elem=10),
        _op("ssd", flops_vu=T * nh * hd * N * 6 / tp,
            flops_sa=(0 if decode else
                      2.0 * T * s["chunk"] * hd * nh * 2 / tp),
            bytes_hbm=scan_bytes,
            mm=None if decode else (T, hd, s["chunk"]),
            sram_demand=16 << 20),
        matmul("out_proj", T, di // tp, D),
    ]


def attention(arch, T, kv_len, decode, tp) -> list[dict]:
    """Grouped-query attention; decode reads all ``T`` sequences' KV."""
    ops = gqa(arch, T, kv_len, decode, tp)
    if decode:
        Hkv, hd = arch["n_kv_heads"], arch["head_dim"]
        ops[1] = dict(ops[1],
                      bytes_hbm=kv_len * Hkv * hd * BF16 * 2 * T / tp)
    return ops


def relu2_mlp(prefix, rows, D, F, split=1, count=1) -> list[dict]:
    """An expert of width ``F`` split ``split`` ways: D -> F, relu²,
    F -> D."""
    return [dict(matmul(f"{prefix}_up", rows, D, F // split), count=count),
            dict(vector(f"{prefix}_relu2", rows * F / split,
                        flops_per_elem=2, bytes_per_elem=0.5), count=count),
            dict(matmul(f"{prefix}_down", rows, F // split, D), count=count)]


def moe(arch, T, n_chips, tp) -> list[dict]:
    """Routed experts of this chip and the shared expert."""
    mo, D = arch["moe"], arch["d_model"]
    E_all, k, F = mo["n_experts"], mo["top_k"], mo["d_ff_expert"]
    ep = math.gcd(E_all, n_chips)
    T_m = max(1, T // tp)               # tokens this chip routes
    R = T_m * k                         # slots its experts receive
    a2a = R * D * BF16 * (ep - 1) / ep
    ops = [matmul("router", T_m, D, E_all),
           vector("router_topk", T_m * E_all, flops_per_elem=4),
           collective("a2a_dispatch", a2a)]
    n = min(E_all // ep, R)
    sizes = [R // n + (1 if e < R % n else 0) for e in range(n)]
    for rows in sorted(set(sizes), reverse=True):
        ops += relu2_mlp("expert", rows, D, F, count=sizes.count(rows))
    ops.append(collective("a2a_combine", a2a))
    if mo["n_shared_experts"]:
        ops += relu2_mlp("shared", T, D, mo["d_ff_shared"], split=tp)
    return ops


def trace(arch: dict, kind: str, seq_len: int, global_batch: int,
          n_chips: int, tp: int) -> list[dict]:
    """The per-chip operator trace of one prefill or decode step of the
    pattern's blocks, then the head."""
    if kind == "train":
        raise ValueError("a train step of the hybrid stack is not traced")
    decode = kind == "decode"
    dp = max(1, n_chips // tp)
    T = max(1, (global_batch if decode else global_batch * seq_len) // dp)
    D = arch["d_model"]
    tail = []
    if tp > 1:
        tail.append(collective("ar_layer", 2 * T * D * BF16 * (tp - 1) / tp))
    tail.append(vector("norms", T * D, flops_per_elem=8))
    mixer = {"M": mamba2(arch, T, decode, tp),
             "*": attention(arch, T, seq_len, decode, tp),
             "E": moe(arch, T, n_chips, tp)}
    ops = [dict(o) for letter in arch["layer_pattern"]
           for o in mixer[letter] + tail]
    ops.append(matmul("lm_head", T, D, vocab_padded(arch) // tp))
    return ops
