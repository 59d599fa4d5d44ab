"""Hybrid Mamba-2 / attention / MoE stacks in the operator trace
(``opgen.arch_workload`` walking ``ArchConfig.layer_pattern``): a reduced
pattern with tensor parallelism and two expert row groups traces as the
plain reference does, the expert-parallel shares add up to the uncut
layer, the ``regate.arch_workload`` span counts the rows each kind of
mixer emitted, the three benchmarked traces keep the terms their
references pin, and a malformed pattern is refused."""
import dataclasses
import glob
import json
import os
import sys

import pytest

from repro.configs.base import (ArchConfig, MoEConfig, ShapeConfig,
                                SSMConfig)
from repro.core import backend
from repro.core.opgen import _deepseek_moe_ops, arch_workload

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)          # bench.*

from bench import program_spans  # noqa: E402
from bench.entries import (common, sweep_grid_hybrid,  # noqa: E402
                           sweep_grid_moe)
from bench.reference import hybrid, mla_moe, opgen  # noqa: E402

NEMOTRON = "nemotron-3-nano-30b-a3b"
REDUCED = ArchConfig(
    name="tiny-hybrid", family="hybrid", n_layers=4, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=0, vocab_size=256, head_dim=16, tie_embeddings=False,
    layer_pattern="ME*E",
    ssm=SSMConfig(d_state=16, head_dim=16, n_groups=2, chunk=8, n_heads=4),
    moe=MoEConfig(n_experts=4, top_k=3, d_ff_expert=32, n_shared_experts=1,
                  act="relu2", d_ff_shared=48))
ROUTED = {"expert_up", "expert_relu2", "expert_down"}
SHARED = {"shared_up", "shared_relu2", "shared_down"}
TAIL = {"ar_layer", "norms"}
OUTSIDE = TAIL | {"mlp_up", "mlp_down", "lm_head"}
MOE_ROWS = ("router", "a2a_", "expert_", "shared_")
MIXER_OF = {"in_proj": "ssm_ops", "qkv": "attn_ops", "router": "moe_ops"}


def _arch(config: dict) -> ArchConfig:
    mod = sweep_grid_moe if "mla" in config["arch"] else common
    return mod.arch_config(config)


def _config(name: str) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as fh:
        return json.load(fh)


def _nemotron() -> ArchConfig:
    return sweep_grid_hybrid.arch_config(_config(NEMOTRON))


def _fields(o) -> tuple:
    return (o.name, o.flops_sa, o.flops_vu, o.bytes_hbm, o.bytes_ici,
            o.sram_demand, o.matmul_dims, o.count, o.collective)


def _ref_fields(r) -> tuple:
    return (r["name"], r["flops_sa"], r["flops_vu"], r["bytes_hbm"],
            r["bytes_ici"], r["sram_demand"], r["mm"], r["count"],
            r["collective"])


def _sum(wl, names, attr) -> float:
    return sum(getattr(o, attr) * o.count for o in wl.ops if o.name in names)


# prefill: 7 tokens, 3 routed a chip, 9 slots over 2 experts (5 and 4);
# decode: 2 sequences, 1 routed a chip, 3 slots (2 and 1)
@pytest.mark.parametrize("shape", [
    ShapeConfig("prefill_tiny", 7, 1, "prefill"),
    ShapeConfig("decode_tiny", 64, 2, "decode")], ids=lambda s: s.kind)
def test_reduced_pattern_is_the_reference_trace(shape):
    wl = arch_workload(REDUCED, shape, n_chips=2, tp=2)
    ref = hybrid.trace(dataclasses.asdict(REDUCED), shape.kind,
                       shape.seq_len, shape.global_batch, 2, 2)
    assert [_fields(o) for o in wl.ops] == [_ref_fields(r) for r in ref]
    names = [o.name for o in wl.ops]
    assert names.count("ar_layer") == names.count("norms") == 4
    ups = [o for o in wl.ops if o.name == "expert_up"]
    # two E blocks, each with its two experts at different rows
    rows = [(2, 1), (1, 1)] if shape.kind == "decode" else [(5, 1), (4, 1)]
    assert [(o.matmul_dims[0], o.count) for o in ups] == rows * 2
    # relu2 is not gated: the up GEMM is D -> F, the shared one F_s / tp
    assert {o.matmul_dims[1:] for o in ups} == {(64, 32)}
    assert {o.matmul_dims[1:] for o in wl.ops if o.name == "shared_up"} \
        == {(64, 24)}
    assert "expert_swiglu" not in names and "mlp_up" not in names


@pytest.mark.parametrize("shape", sorted(_config(NEMOTRON)["shapes"]))
def test_expert_parallel_shares_add_up_to_the_uncut_layer(shape):
    sh = _config(NEMOTRON)["shapes"][shape]
    cfg, n = _nemotron(), sh["n_chips"]
    s = ShapeConfig(shape, sh["seq_len"], sh["global_batch"], sh["kind"])
    share = arch_workload(cfg, s, n_chips=n, tp=1)
    whole = arch_workload(cfg, s, n_chips=1, tp=1)
    # 16 of 128 experts a chip, each chip routing its eighth of the tokens
    for attr in ("flops_sa", "flops_vu", "bytes_hbm"):
        assert n * _sum(share, ROUTED, attr) == _sum(whole, ROUTED, attr)
    # the shared expert runs on every chip's own tokens: its work adds up,
    # its weights (2688 x 3712, twice, in bf16, a layer) count once
    for attr in ("flops_sa", "flops_vu"):
        assert n * _sum(share, SHARED, attr) == _sum(whole, SHARED, attr)
    weights = 23 * 2 * 2688 * 3712 * 2
    assert n * _sum(share, SHARED, "bytes_hbm") - (n - 1) * weights \
        == _sum(whole, SHARED, "bytes_hbm")
    assert _sum(whole, {"a2a_dispatch"}, "bytes_ici") == 0
    assert _sum(share, {"a2a_dispatch"}, "bytes_ici") > 0


def test_nemotron_blocks_carry_the_published_terms():
    cfg = _nemotron()
    wl = arch_workload(cfg, ShapeConfig("decode_32k", 32768, 2048,
                                        "decode"), n_chips=8, tp=1)
    by = {}
    for o in wl.ops:
        by.setdefault(o.name, []).append(o)
    assert len(by["in_proj"]) == len(by["router"]) == 23
    assert len(by["qkv"]) == 6 and len(by["norms"]) == 52
    T = 256
    # x, z, B, C and dt columns; the conv over x, B and C
    assert by["in_proj"][0].matmul_dims == (T, 2688, 2 * 4096 + 2048 + 64)
    assert by["conv+act"][0].bytes_hbm == T * 6144 * 4
    # each sequence's 64 x 64 x 128 state, read and written, in bf16
    assert by["ssd"][0].bytes_hbm == T * 4096 * 2 * 3 + T * 64 * 64 * 128 * 4
    # every sequence's cache: 2 kv heads x 128, k and v, in bf16
    assert by["attn_decode"][0].bytes_hbm == T * 32768 * 2 * 128 * 2 * 2
    assert by["shared_up"][0].matmul_dims == (T, 2688, 3712)


@pytest.mark.parametrize("name", ["qwen2.5-14b", "mamba2-780m",
                                  "deepseek-v2-236b"])
def test_benchmarked_traces_keep_their_references(name):
    # the dense, SSM and MoE blocks the hybrid shares keep the decode KV
    # factor, the SSM projection and the state-free scan their
    # references pin
    config = _config(name)
    arch, ref = _arch(config), (mla_moe.trace if "mla" in config["arch"]
                                      else opgen.trace)
    for shape, sh in config["shapes"].items():
        for batch in (1, sh["global_batch"]):
            wl = arch_workload(arch, ShapeConfig(shape, sh["seq_len"], batch,
                                                 sh["kind"]),
                               n_chips=sh["n_chips"], tp=sh["tp"])
            assert [_fields(o) for o in wl.ops] == [
                _ref_fields(r) for r in ref(config["arch"], sh["kind"],
                                            sh["seq_len"], batch,
                                            sh["n_chips"], sh["tp"])]


def _rows_by_mixer(wl, latent: bool) -> dict:
    """The rows of each block's mixer: a dense or SSM block found by its
    first op and ended by the tail (the SSM stack has none, so the head
    ends it); latent attention's rows are those of no other kind."""
    out = dict.fromkeys(MIXER_OF.values(), 0)
    if latent:
        out["moe_ops"] = sum(o.name.startswith(MOE_ROWS) for o in wl.ops)
        out["attn_ops"] = len(wl.ops) - out["moe_ops"] - sum(
            o.name in OUTSIDE for o in wl.ops)
        return out
    kind = None
    for o in wl.ops:
        if o.name in OUTSIDE:
            kind = None if o.name not in ("mlp_up", "mlp_down") else kind
            continue
        kind = kind or MIXER_OF[o.name]
        out[kind] += 1
    return out


def test_span_counts_the_rows_each_kind_of_mixer_emitted(tmp_path):
    import jax
    calls = [(_nemotron(), ShapeConfig("decode_256k", 262144, 256,
                                       "decode"), 8, 1),
             (REDUCED, ShapeConfig("prefill_tiny", 7, 1, "prefill"), 2, 2)]
    for name in ("qwen2.5-14b", "mamba2-780m", "deepseek-v2-236b"):
        config = _config(name)
        sh = config["shapes"]["decode_32k"]
        calls.append((_arch(config),
                      ShapeConfig("decode_32k", sh["seq_len"],
                                  sh["global_batch"], "decode"),
                      sh["n_chips"], sh["tp"]))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        wls = [arch_workload(a, s, n_chips=n, tp=tp) for a, s, n, tp in calls]
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    got = [st for name, _t, st in program_spans.load(path)["counts"]
           if name == "regate.arch_workload"]
    assert len(got) == len(calls)
    for (arch, _s, _n, _tp), wl, st in zip(calls, wls, got):
        want = _rows_by_mixer(wl, arch.mla is not None)
        assert st == dict(want, layers=arch.n_layers, ops=len(wl.ops)), \
            arch.name
    nemotron = got[0]
    assert (nemotron["ssm_ops"], nemotron["attn_ops"]) == (23 * 4, 6 * 3)
    assert nemotron["moe_ops"] == 23 * len(_deepseek_moe_ops(
        calls[0][0].moe, 32, 2688, n_chips=8, tp=1))


def test_host_span_opens_nothing_without_a_recording_profiler():
    with backend.host_span("regate.arch_workload") as ann:
        assert ann is None


@pytest.mark.parametrize("change,match", [
    (dict(layer_pattern="ME-E"), "letters"),
    (dict(layer_pattern="ME*"), "n_layers"),
    (dict(moe=dataclasses.replace(REDUCED.moe, act="gelu")), "activation")])
def test_malformed_pattern_is_refused(change, match):
    cfg = dataclasses.replace(REDUCED, **change)
    with pytest.raises(ValueError, match=match):
        arch_workload(cfg, ShapeConfig("p", 8, 1, "prefill"), n_chips=2,
                      tp=2)


def test_train_step_of_a_pattern_is_refused():
    with pytest.raises(ValueError, match="train"):
        arch_workload(REDUCED, ShapeConfig("t", 8, 2, "train"), n_chips=2,
                      tp=2)


def test_ssm_heads_set_the_inner_width():
    assert SSMConfig(head_dim=64, n_heads=64).d_inner(2688) == 4096
    assert SSMConfig(head_dim=64).d_inner(1536) == 3072
    assert SSMConfig(head_dim=64).heads(1536) == 48
