"""The plain reference of hybrid Mamba-2 / attention / MoE stacks
(``bench/reference/hybrid.py``) against the program, and its control:
op for op at the configuration's shapes and every batch fraction of the
``sweep_hybrid`` mix, and record for record on the numpy and jax paths
of the program. The ``knob_resweep`` mix sweeps one deployment traced
at set-up, checked by ``sweep_grid``'s reference."""
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
from bench.entries import (sweep_grid, sweep_grid_fixed,  # noqa: E402
                           sweep_grid_hybrid)
from bench.reference import hybrid  # noqa: E402

NT = "nemotron-3-nano-30b-a3b"
SEEDS = (1, 2 ** 31 + 7, 4242)


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "bench", kind, name + ".json")) as fh:
        return json.load(fh)


def test_arch_block_restates_the_published_config():
    config = _load("configs", NT)
    a, s, mo = (config["arch"], config["arch"]["ssm"],
                config["arch"]["moe"])
    assert (a["n_layers"], a["d_model"], a["n_heads"], a["n_kv_heads"],
            a["head_dim"], a["vocab_size"], a["layer_pattern"],
            a["tie_embeddings"]) == (
        config["num_hidden_layers"], config["hidden_size"],
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"], config["vocab_size"],
        config["hybrid_override_pattern"], config["tie_word_embeddings"])
    assert (s["n_heads"], s["head_dim"], s["d_state"], s["n_groups"],
            s["conv_width"], s["chunk"]) == (
        config["mamba_num_heads"], config["mamba_head_dim"],
        config["ssm_state_size"], config["n_groups"], config["conv_kernel"],
        config["chunk_size"])
    assert (mo["n_experts"], mo["top_k"], mo["d_ff_expert"],
            mo["n_shared_experts"], mo["d_ff_shared"], mo["act"]) == (
        config["n_routed_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"], config["n_shared_experts"],
        config["moe_shared_expert_intermediate_size"],
        config["mlp_hidden_act"])
    assert config["reduced"] == []


def _fractions():
    config, traffic = _load("configs", NT), _load("traffic", "sweep_hybrid")
    return [(shape, f) for shape in sorted(config["shapes"])
            for f in traffic["batch_fractions"]]


@pytest.mark.parametrize("shape,frac", _fractions())
def test_reference_trace_is_the_program_trace(shape, frac):
    from repro.configs.base import ShapeConfig
    from repro.core.opgen import arch_workload
    config = _load("configs", NT)
    sh = config["shapes"][shape]
    b = max(1, int(sh["global_batch"] * frac))
    wl = arch_workload(sweep_grid_hybrid.arch_config(config),
                       ShapeConfig(shape, sh["seq_len"], b, sh["kind"]),
                       n_chips=sh["n_chips"], tp=sh["tp"])
    ref = hybrid.trace(config["arch"], sh["kind"], sh["seq_len"], b,
                       sh["n_chips"], sh["tp"])
    assert len(ref) == len(wl.ops)
    for r, o in zip(ref, wl.ops):
        assert (r["name"], r["flops_sa"], r["flops_vu"], r["bytes_hbm"],
                r["bytes_ici"], r["sram_demand"], r["mm"], r["count"],
                r["collective"]) == (
            o.name, o.flops_sa, o.flops_vu, o.bytes_hbm, o.bytes_ici,
            o.sram_demand, o.matmul_dims, o.count, o.collective)


def _small(q: dict) -> dict:
    """The query on a small knob grid: two values of two of its axes."""
    return dict(q, axes={"delay_scale": q["axes"]["delay_scale"][:2],
                         "leak_off_logic": q["axes"]["leak_off_logic"][:2]})


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_reference_agrees_with_the_program(backend):
    from repro.core.policies import KnobGrid, evaluate_batch
    config, traffic = _load("configs", NT), _load("traffic", "sweep_hybrid")
    q = _small(generator.query(config, traffic, 2 ** 31 + 23, 3))
    wls = sweep_grid_hybrid.Entry(config, traffic).build(q)
    recs = evaluate_batch(wls, tuple(q["npus"]), tuple(q["policies"]),
                          KnobGrid(**q["axes"]), backend=backend).records()
    assert len(recs) == sweep_grid_hybrid.Entry.size(q) == 300
    idx = sorted(generator.stream(5, 9).choice(len(recs), 60,
                                               replace=False).tolist())
    dev = compare.deviation(sweep_grid_hybrid.reference(config, q, idx),
                            [recs[i] for i in idx], sweep_grid_hybrid.EXACT,
                            sweep_grid_hybrid.FLOOR)
    assert dev["mismatches"] == 0, dev["first"]
    assert dev["max_rel_dev"] <= sweep_grid_hybrid.LIMIT / 100


def test_float32_control_fails_the_comparison():
    config = _load("configs", NT)
    q = generator.query(config, _load("traffic", "sweep_hybrid"), 37, 3)
    idx = sorted(generator.stream(37, 9).choice(18000, 40,
                                                replace=False).tolist())
    ref = sweep_grid_hybrid.reference(config, q, idx)
    ctl = sweep_grid_hybrid.reference(config, q, idx, np.float32)
    dev = compare.deviation(ref, ctl, sweep_grid_hybrid.EXACT,
                            sweep_grid_hybrid.FLOOR)
    assert dev["max_rel_dev"] > 100 * sweep_grid_hybrid.LIMIT \
        or dev["mismatches"] > 0


def test_sweep_shapes_fixed_across_seeds():
    # decode_256k at an eighth of its batch splits the experts into two
    # row groups, at the other fractions into one: the entry sweeps both
    # stacks at set-up, every stack the seeds draw is one of them, and no
    # query compiles
    import jax
    from repro.core.opgen import stack_traces
    from repro.core.policies import KnobGrid, _gap_indices, knob_pairs
    config, traffic = _load("configs", NT), _load("traffic", "sweep_hybrid")
    entry = sweep_grid_hybrid.Entry(config, traffic)

    def stack_shape(q, wls):
        st = stack_traces(wls)
        gaps = {c: len(v[1]) for c, v in _gap_indices(st).items()}
        triples, _ = knob_pairs(KnobGrid(**q["axes"]).product())
        return (st.n_ops, tuple(sorted(gaps.items())), len(triples),
                entry.size(q))

    warmed = {stack_shape(q, entry.build(q))
              for q in entry.warm_queries(config, traffic)}
    assert sorted(s[0] for s in warmed) == [3 * 393, 2 * 393 + 462]
    assert {s[2:] for s in warmed} == {(6, 18000)}
    compiles: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _d, **_kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    seen, fracs = set(), set()
    for seed in SEEDS:
        for i in range(6):
            q = generator.query(config, traffic, seed, i)
            fracs |= {w["global_batch"] for w in q["workloads"]}
            wls = entry.build(q)
            seen.add(stack_shape(q, wls))
            entry.call(q, wls)
    assert seen == warmed
    assert compiles == []
    assert len(fracs) > 3


def test_sweep_hybrid_is_the_sweep_mix():
    mix, sweep = _load("traffic", "sweep_hybrid"), _load("traffic", "sweep")
    assert dict(mix, entry="sweep_grid") == sweep


def test_entry_refuses_a_program_without_the_pattern(monkeypatch):
    # a program that prices the model by its family (attention and
    # DeepSeekMoE in every layer, as before it walked the pattern) fails
    # at set-up
    from repro.core import opgen
    config, traffic = _load("configs", NT), _load("traffic", "sweep_hybrid")
    plain = dataclasses.replace(sweep_grid_hybrid.arch_config(config),
                                layer_pattern="")
    real = opgen.arch_workload
    monkeypatch.setattr(opgen, "arch_workload",
                        lambda arch, *a, **kw: real(plain, *a, **kw))
    with pytest.raises(RuntimeError, match="lacks the reference's ops"):
        sweep_grid_hybrid.Entry(config, traffic)
    monkeypatch.setattr(opgen, "arch_workload", real)
    assert sweep_grid_hybrid.Entry(config, traffic).arch.layer_pattern


def test_knob_resweep_sweeps_one_deployment_with_fresh_knobs():
    from repro.core.opgen import stack_traces
    from repro.core.policies import KnobGrid, evaluate_batch
    config, traffic = (_load("configs", "qwen2.5-14b"),
                       _load("traffic", "knob_resweep"))
    assert dict(traffic, entry="sweep_grid", batch_fractions=[
        0.125, 0.25, 0.5, 1.0]) == _load("traffic", "sweep")
    entry = sweep_grid_fixed.Entry(config, traffic)
    qs = [generator.query(config, traffic, 2 ** 31 + 5, i) for i in (2, 3)]
    assert qs[0]["workloads"] == qs[1]["workloads"]
    assert qs[0]["axes"] != qs[1]["axes"]
    a, b = entry.build(qs[0]), entry.build(qs[1])
    assert all(x is y for x, y in zip(a, b))
    assert stack_traces(a) is stack_traces(b)
    q = _small(qs[1])
    recs = evaluate_batch(b, tuple(q["npus"]), tuple(q["policies"]),
                          KnobGrid(**q["axes"]), backend="numpy").records()
    idx = sorted(generator.stream(6, 9).choice(len(recs), 40,
                                               replace=False).tolist())
    dev = compare.deviation(sweep_grid_fixed.reference(config, q, idx),
                            [recs[i] for i in idx], sweep_grid.EXACT,
                            sweep_grid.FLOOR)
    assert dev["mismatches"] == 0, dev["first"]
    assert dev["max_rel_dev"] <= sweep_grid.LIMIT / 100


def test_knob_resweep_refuses_drawn_batches():
    config = _load("configs", "qwen2.5-14b")
    with pytest.raises(ValueError, match="batch_fractions"):
        sweep_grid_fixed.Entry(config, _load("traffic", "sweep"))
