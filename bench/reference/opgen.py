"""Operator traces of a deployment, restated from the published model.

Builds, from a configuration file's ``arch`` block and one shape, the
per-chip operator list the simulator prices: for each op its SA and VU
FLOPs, HBM and ICI bytes, resident SRAM tile, matmul dims and repeat
count. Dense attention blocks (grouped-query heads, SwiGLU MLP) and
Mamba-2 SSD blocks are covered; a training step counts the backward
pass as two more forward passes and ends with the gradient all-reduce
and the Adam update over the model's parameters.

Each op is a plain dict; a trace is a list of them in execution order.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def _op(name, flops_sa=0.0, flops_vu=0.0, bytes_hbm=0.0, bytes_ici=0.0,
        sram_demand=0, mm=None, count=1, collective=False) -> dict:
    return {"name": name, "flops_sa": flops_sa, "flops_vu": flops_vu,
            "bytes_hbm": bytes_hbm, "bytes_ici": bytes_ici,
            "sram_demand": sram_demand, "mm": mm, "count": count,
            "collective": collective}


def matmul(name, M, K, N) -> dict:
    """[M,K]x[K,N] in bf16, weights and activations streamed from HBM;
    the SRAM tile is the weight-stationary working set for M >= 512 and
    a latency-hiding double buffer below; the VU post-processes every
    output element."""
    flops = 2.0 * M * K * N
    b = K * N * BF16 + M * K * BF16 * 1.0 + M * N * BF16
    if M >= 512:
        tile = min(int(0.75 * 128 * 2 ** 20),
                   K * N * BF16 + 2 * 512 * K * BF16 + 512 * N * F32)
    else:
        tile = min(8 << 20, b)
    return _op(name, flops_sa=flops / 1, flops_vu=M * N * 2.0 / 1,
               bytes_hbm=b / 1, sram_demand=int(tile), mm=(M, K, N))


def vector(name, elems, flops_per_elem=2.0, bytes_per_elem=2 * BF16,
           sram_tile=4 << 20) -> dict:
    return _op(name, flops_vu=elems * flops_per_elem / 1,
               bytes_hbm=elems * bytes_per_elem / 1, sram_demand=sram_tile)


def collective(name, bytes_per_chip, sram_tile=8 << 20) -> dict:
    return _op(name, bytes_ici=bytes_per_chip, sram_demand=sram_tile,
               collective=True)


def vocab_padded(arch: dict) -> int:
    """The vocabulary rounded up to a multiple of 256 (16-way tensor
    parallel divisibility), as the simulated model lays it out."""
    v = arch["vocab_size"]
    return (v + 255) // 256 * 256


def n_params(arch: dict) -> int:
    """Parameters of the model, counted from its published layout."""
    D, V, L = arch["d_model"], vocab_padded(arch), arch["n_layers"]
    total = V * D + D                         # embedding, final norm
    if not arch["tie_embeddings"]:
        total += D * V                        # output head
    if arch["family"] == "ssm":
        s = arch["ssm"]
        di = s["expand"] * D
        nh = di // s["head_dim"]
        gn = s["n_groups"] * s["d_state"]
        w = s["conv_width"]
        per = (D * (2 * di + 2 * gn + nh)     # in_x, in_z, in_B, in_C, in_dt
               + (w + 1) * (di + 2 * gn)      # conv taps and bias
               + 3 * nh                       # A_log, D skip, dt bias
               + di + D                       # gated norm, layer norm
               + di * D)                      # out_proj
    else:
        H, Hkv, hd, ff = (arch["n_heads"], arch["n_kv_heads"],
                          arch["head_dim"], arch["d_ff"])
        per = (2 * D                          # two layer norms
               + D * H * hd + 2 * D * Hkv * hd + H * hd * D
               + 3 * D * ff)
        if arch["qkv_bias"]:
            per += H * hd + 2 * Hkv * hd
    return total + L * per


def trace(arch: dict, kind: str, seq_len: int, global_batch: int,
          n_chips: int, tp: int) -> list[dict]:
    """The per-chip operator trace of one step of ``kind`` (train,
    prefill or decode) at ``seq_len`` x ``global_batch`` on a slice of
    ``n_chips`` chips with ``tp``-way tensor parallelism (the rest data
    parallel)."""
    decode = kind == "decode"
    train = kind == "train"
    dp = max(1, n_chips // tp)
    T = max(1, (global_batch if decode else global_batch * seq_len) // dp)
    D = arch["d_model"]
    kv_len = seq_len
    layer: list[dict] = []
    if arch["family"] == "ssm":
        s = arch["ssm"]
        di = s["expand"] * D
        nh = di // s["head_dim"]
        layer = [
            matmul("in_proj", T, D, 2 * di // tp),
            vector("conv+act", T * di / tp, flops_per_elem=10),
            _op("ssd", flops_vu=T * nh * s["head_dim"] * s["d_state"] * 6
                / tp,
                flops_sa=(0 if decode else
                          2.0 * T * s["chunk"] * s["head_dim"] * nh * 2 / tp),
                bytes_hbm=T * di * BF16 * 3 / tp,
                mm=None if decode else (T, s["head_dim"], s["chunk"]),
                sram_demand=16 << 20),
            matmul("out_proj", T, di // tp, D),
        ]
    else:
        H, Hkv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
        layer.append(matmul("qkv", T, D, (H + 2 * Hkv) * hd // tp))
        if decode:
            layer.append(_op(
                "attn_decode",
                flops_vu=2.0 * T * kv_len * hd * 2 * H / tp,
                bytes_hbm=kv_len * Hkv * hd * BF16 * 2 * max(1, T // 8) / tp,
                sram_demand=8 << 20))
        else:
            layer.append(_op(
                "attention", flops_sa=2.0 * T * kv_len * hd * 2 * H / tp,
                bytes_hbm=3 * T * D * BF16 / tp, mm=(T, hd, kv_len),
                sram_demand=24 << 20))
        layer.append(matmul("out_proj", T, H * hd // tp, D))
        layer.append(matmul("mlp_up", T, D, 2 * arch["d_ff"] // tp))
        layer.append(matmul("mlp_down", T, arch["d_ff"] // tp, D))
        if tp > 1:
            layer.append(collective("ar_layer",
                                    2 * T * D * BF16 * (tp - 1) / tp))
        layer.append(vector("norms", T * D, flops_per_elem=8))
    mult = 3 if train else 1                      # forward + 2x backward
    layer = [dict(o, flops_sa=o["flops_sa"] * mult,
                  flops_vu=o["flops_vu"] * mult,
                  bytes_hbm=o["bytes_hbm"] * mult) for o in layer]
    ops = [dict(o) for _ in range(arch["n_layers"]) for o in layer]
    ops.append(matmul("lm_head", T, D, vocab_padded(arch) // tp))
    if train:
        p = n_params(arch)
        ops.append(collective("grad_allreduce", 2 * p * BF16 / (tp * dp)))
        ops.append(vector("adam", p / (tp * dp), flops_per_elem=12,
                          bytes_per_elem=16))
    return ops
