"""The plain reference of latent attention and DeepSeekMoE
(``bench/reference/mla_moe.py``) against the program, and its control:
op for op at the configuration's shapes and at the simulator's default
deployment, parameter for parameter, and record for record on the
numpy path of the program (the chip run compares the jax path the same
way). The ``sweep`` mix on the SSM stack goes through the existing
entry and reference."""
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import compare, generator  # noqa: E402
from bench.entries import sweep_grid, sweep_grid_moe  # noqa: E402
from bench.reference import mla_moe  # noqa: E402

DS = "deepseek-v2-236b"


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "bench", kind, name + ".json")) as fh:
        return json.load(fh)


def _same_ops(ref: list, wl) -> None:
    assert len(ref) == len(wl.ops)
    for r, o in zip(ref, wl.ops):
        assert (r["name"], r["flops_sa"], r["flops_vu"], r["bytes_hbm"],
                r["bytes_ici"], r["sram_demand"], r["mm"], r["count"],
                r["collective"]) == (
            o.name, o.flops_sa, o.flops_vu, o.bytes_hbm, o.bytes_ici,
            o.sram_demand, o.matmul_dims, o.count, o.collective)


def test_config_file_is_the_registered_model():
    from repro.configs.base import get_arch
    from repro.models.registry import count_params
    config = _load("configs", DS)
    got = sweep_grid_moe.arch_config(config)
    assert dataclasses.replace(got, source="") == dataclasses.replace(
        get_arch(DS), source="")
    assert mla_moe.n_params(config["arch"]) == count_params(got) \
        == 235_741_434_880
    # the published keys the arch block restates
    a, m, mo = config["arch"], config["arch"]["mla"], config["arch"]["moe"]
    assert (a["n_layers"], a["d_model"], a["n_heads"], a["d_ff"],
            a["vocab_size"]) == (
        config["num_hidden_layers"], config["hidden_size"],
        config["num_attention_heads"], config["intermediate_size"],
        config["vocab_size"])
    assert (m["q_lora_rank"], m["kv_lora_rank"], m["nope_head_dim"],
            m["rope_head_dim"], m["v_head_dim"]) == (
        config["q_lora_rank"], config["kv_lora_rank"],
        config["qk_nope_head_dim"], config["qk_rope_head_dim"],
        config["v_head_dim"])
    assert (mo["n_experts"], mo["top_k"], mo["d_ff_expert"],
            mo["n_shared_experts"], mo["first_dense_layers"]) == (
        config["n_routed_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"], config["n_shared_experts"],
        config["first_k_dense_replace"])


@pytest.mark.parametrize("shape", sorted(_load("configs", DS)["shapes"]))
@pytest.mark.parametrize("batch", ["one", "published"])
def test_reference_trace_is_the_program_trace(shape, batch):
    from repro.configs.base import ShapeConfig
    from repro.core.opgen import arch_workload
    config = _load("configs", DS)
    sh = config["shapes"][shape]
    b = 1 if batch == "one" else sh["global_batch"]
    wl = arch_workload(sweep_grid_moe.arch_config(config),
                       ShapeConfig(shape, sh["seq_len"], b, sh["kind"]),
                       n_chips=sh["n_chips"], tp=sh["tp"])
    _same_ops(mla_moe.trace(config["arch"], sh["kind"], sh["seq_len"], b,
                            sh["n_chips"], sh["tp"]), wl)


@pytest.mark.parametrize("name", [DS, "granite-moe-1b-a400m"])
@pytest.mark.parametrize("shape", ["train_4k", "prefill_32k", "decode_32k"])
def test_reference_trace_at_the_default_deployment(name, shape):
    # arch_workload's defaults: 256 chips, 16-way tensor parallel
    from repro.configs.base import SHAPES, get_arch
    from repro.core.opgen import arch_workload
    cfg, sh = get_arch(name), SHAPES[shape]
    _same_ops(mla_moe.trace(dataclasses.asdict(cfg), sh.kind, sh.seq_len,
                            sh.global_batch, 256, 16),
              arch_workload(cfg, sh))


def _records(config, traffic, mod, seed, n=40):
    from repro.core.policies import KnobGrid, evaluate_batch
    q = generator.query(config, traffic, seed, 3)
    wls = mod.Entry(config, traffic).build(q)
    recs = evaluate_batch(wls, tuple(q["npus"]), tuple(q["policies"]),
                          KnobGrid(**q["axes"]), backend="numpy").records()
    assert len(recs) == mod.Entry.size(q)
    idx = sorted(generator.stream(seed, 9).choice(len(recs), n,
                                                  replace=False).tolist())
    return q, idx, [recs[i] for i in idx]


CASES = {"deepseek": (DS, "sweep_moe", sweep_grid_moe),
         "mamba2": ("mamba2-780m", "sweep", sweep_grid)}


@pytest.mark.parametrize("cell", sorted(CASES))
def test_reference_agrees_with_the_program(cell):
    name, mix, mod = CASES[cell]
    config, traffic = _load("configs", name), _load("traffic", mix)
    assert traffic["entry"] == mod.__name__.rsplit(".", 1)[1]
    q, idx, got = _records(config, traffic, mod, 2 ** 31 + 23)
    dev = compare.deviation(mod.reference(config, q, idx), got, mod.EXACT,
                            mod.FLOOR)
    assert dev["mismatches"] == 0, dev["first"]
    assert dev["max_rel_dev"] <= mod.LIMIT / 100


def test_float32_control_fails_the_comparison():
    config = _load("configs", DS)
    q = generator.query(config, _load("traffic", "sweep_moe"), 37, 3)
    idx = sorted(generator.stream(37, 9).choice(18000, 40,
                                                replace=False).tolist())
    ref = sweep_grid_moe.reference(config, q, idx)
    ctl = sweep_grid_moe.reference(config, q, idx, np.float32)
    dev = compare.deviation(ref, ctl, sweep_grid_moe.EXACT,
                            sweep_grid_moe.FLOOR)
    assert dev["max_rel_dev"] > 100 * sweep_grid_moe.LIMIT \
        or dev["mismatches"] > 0


def test_sweep_moe_is_the_sweep_mix():
    moe, sweep = _load("traffic", "sweep_moe"), _load("traffic", "sweep")
    assert dict(moe, entry="sweep_grid") == sweep


def test_entry_refuses_a_program_without_the_new_ops(monkeypatch):
    # a program that prices the model as a dense block (as before it
    # traced latent attention and routed experts) fails at set-up
    from repro.core import opgen
    config = _load("configs", DS)
    plain = dataclasses.replace(sweep_grid_moe.arch_config(config),
                                mla=None, moe=None)
    real = opgen.arch_workload
    monkeypatch.setattr(opgen, "arch_workload",
                        lambda arch, *a, **kw: real(plain, *a, **kw))
    with pytest.raises(RuntimeError, match="lacks the reference's ops"):
        sweep_grid_moe.Entry(config, _load("traffic", "sweep_moe"))
    monkeypatch.setattr(opgen, "arch_workload", real)
    entry = sweep_grid_moe.Entry(config, _load("traffic", "sweep_moe"))
    assert entry.arch.mla is not None and entry.arch.moe is not None
