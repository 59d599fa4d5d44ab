"""Jitter-plane perturbation engine + differential fuzz (ISSUE 6).

Covers the determinism contract (same Generator seed -> bit-identical
perturbed traces), the conservation invariants of each transform, the
severity axis (0 = exact identity), the perturbed-stack sweep
equivalence (numpy batched vs scalar oracle; jax vs numpy when jax is
present), and the >= 200-program EventTimeline-vs-VLIWTimeline
differential fuzz harness.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.isa import (EventTimeline, Instr, PMode, VLIWTimeline,
                            expand_events, merge_events, setpm)
from repro.core.opgen import dlrm_workload, llm_workload
from repro.core.perturb import (FUZZ_KW, BurstCompression, ClockJitter,
                                IdleFragmentation, LinkDegradation,
                                Straggler, adversarial_events,
                                differential_fuzz, perturb_suite,
                                perturb_workload, severity_plan)
from repro.core.policies import PolicyKnobs, evaluate, evaluate_batch

from _sweep_equiv import rel

WL = llm_workload("llama3-8b", "decode", batch=8, n_chips=8, tp=8, dp=1)
PLAN = severity_plan(1.0)


def _cols(wl):
    return {
        "flops_sa": np.array([o.flops_sa for o in wl.ops]),
        "flops_vu": np.array([o.flops_vu for o in wl.ops]),
        "bytes_hbm": np.array([o.bytes_hbm for o in wl.ops]),
        "bytes_ici": np.array([o.bytes_ici for o in wl.ops]),
        "count": np.array([float(o.count) for o in wl.ops]),
    }


# ---------------------------------------------------------------- determinism

@pytest.mark.parametrize("seed", [0, 7, 1234])
def test_perturb_deterministic_under_fixed_seed(seed):
    a = perturb_workload(WL, PLAN, np.random.default_rng(seed))
    b = perturb_workload(WL, PLAN, np.random.default_rng(seed))
    assert a.ops == b.ops  # Op is a frozen dataclass: exact equality
    c = perturb_workload(WL, PLAN, np.random.default_rng(seed + 1))
    assert a.ops != c.ops


def test_perturb_suite_order_stable():
    wls = [WL, dlrm_workload("S"), dlrm_workload("M")]
    full = perturb_suite(wls, PLAN, seed=3)
    # dropping workload 1 must not change workload 2's perturbation ...
    assert perturb_suite([wls[0], wls[2]], PLAN, seed=3)[0].ops \
        == full[0].ops
    # ... because child generators key on (seed, stream, index)
    assert perturb_suite(wls, PLAN, seed=3, stream=1)[0].ops \
        != full[0].ops


def test_perturb_requires_explicit_generator():
    with pytest.raises(TypeError, match="Generator"):
        perturb_workload(WL, PLAN, 1234)
    with pytest.raises(TypeError, match="Generator"):
        BurstCompression().apply(_cols(WL), np.random.RandomState(0))


# --------------------------------------------------------------- conservation

def test_severity_zero_is_exact_identity():
    assert severity_plan(0.0) == ()
    out = perturb_workload(WL, (), np.random.default_rng(0), name="x")
    assert out.name == "x"
    assert out.ops == WL.ops


def test_severity_plan_validates():
    with pytest.raises(ValueError):
        severity_plan(-0.5)
    with pytest.raises(ValueError):
        severity_plan(float("nan"))


def test_burst_compression_conserves_wire_bytes():
    # topology lowering turns each collective into a run of step ops —
    # the multi-op ICI-active runs burst compression acts on (pure
    # byte split: staging ops would break up the contiguous runs)
    from repro.core.ici_topology import lower_collectives
    wl = lower_collectives(WL, staging=False)
    cols = _cols(wl)
    total = (cols["bytes_ici"] * cols["count"]).sum()
    cols["collective"] = np.array([o.collective for o in wl.ops])
    out = BurstCompression(factor=3.0).apply(cols, np.random.default_rng(0))
    assert rel((out["bytes_ici"] * out["count"]).sum(), total) <= 1e-9
    # bursts are denser: strictly fewer ICI-active ops
    assert (out["bytes_ici"] > 0).sum() < sum(
        o.bytes_ici > 0 for o in wl.ops)


def test_idle_fragmentation_conserves_totals():
    wl = perturb_workload(WL, [IdleFragmentation(factor=8)],
                          np.random.default_rng(0))
    for f in ("flops_sa", "flops_vu", "bytes_hbm", "bytes_ici"):
        a = sum(getattr(o, f) * o.count for o in WL.ops)
        b = sum(getattr(o, f) * o.count for o in wl.ops)
        assert rel(a, b) <= 1e-9, f
    assert sum(o.count for o in wl.ops) > sum(o.count for o in WL.ops)


def test_transform_param_validation():
    for bad in (lambda: BurstCompression(factor=0.5),
                lambda: LinkDegradation(rate=0.0),
                lambda: LinkDegradation(rate=1.5),
                lambda: LinkDegradation(window_frac=0.0),
                lambda: Straggler(slowdown=0.9),
                lambda: Straggler(frac=1.5),
                lambda: IdleFragmentation(factor=0),
                lambda: IdleFragmentation(factor=2.5),
                lambda: ClockJitter(sigma=-0.1)):
        with pytest.raises(ValueError):
            bad()


def test_composition_draw_counts_fixed():
    """A no-op transform must still consume its rng draws, so a
    composed plan's downstream transforms see the same stream whether
    or not earlier ones fired."""
    plan_a = (Straggler(slowdown=1.0, frac=0.0), ClockJitter(sigma=0.02))
    plan_b = (Straggler(slowdown=2.0, frac=0.0), ClockJitter(sigma=0.02))
    a = perturb_workload(WL, plan_a, np.random.default_rng(5))
    b = perturb_workload(WL, plan_b, np.random.default_rng(5))
    assert a.ops == b.ops


# ----------------------------------------------- perturbed sweep equivalence

def test_perturbed_stack_numpy_matches_scalar_oracle():
    pert = perturb_suite([WL, dlrm_workload("S")], severity_plan(1.5),
                         seed=11)
    grid = (PolicyKnobs(window_scale=0.25), PolicyKnobs(),
            PolicyKnobs(window_scale=4.0, delay_scale=2.0))
    pols = ("ReGate-HW", "ReGate-Full", "NoPG")
    res = evaluate_batch(pert, ("NPU-D",), pols, grid, backend="numpy")
    for wi, wl in enumerate(pert):
        for pi, pol in enumerate(pols):
            for ki, kn in enumerate(grid):
                ref = evaluate(wl, "NPU-D", pol, kn)
                got = res.report(wi, 0, pi, ki)
                assert rel(ref.runtime_s, got.runtime_s) <= 1e-9
                assert rel(ref.total_j, got.total_j) <= 1e-9
                for c in ref.static_j:
                    assert rel(ref.static_j[c], got.static_j[c]) \
                        <= 1e-9, (wl.name, pol, ki, c)


def test_perturbed_stack_jax_matches_numpy():
    pytest.importorskip("jax")
    pert = perturb_suite([WL, dlrm_workload("S")], severity_plan(2.0),
                         seed=2)
    grid = (PolicyKnobs(window_scale=1 / 16), PolicyKnobs(),
            PolicyKnobs(window_scale=4.0))
    pols = ("ReGate-HW", "NoPG")
    bn = evaluate_batch(pert, ("NPU-C", "NPU-D"), pols, grid,
                        backend="numpy")
    bj = evaluate_batch(pert, ("NPU-C", "NPU-D"), pols, grid,
                        backend="jax")
    assert np.allclose(bn.runtime_s, bj.runtime_s, rtol=1e-9, atol=0)
    for c in bn.static_j:
        assert np.allclose(bn.static_j[c], bj.static_j[c],
                           rtol=1e-9, atol=1e-9), c
        assert np.allclose(bn.dynamic_j[c], bj.dynamic_j[c],
                           rtol=1e-9, atol=1e-9), c


# ------------------------------------------------------------------- fuzzing

def test_adversarial_events_are_canonical():
    events, horizon = adversarial_events(np.random.default_rng(0))
    cycles = [c for c, _ in events]
    assert cycles == sorted(cycles)
    assert len(set(cycles)) == len(cycles)  # merge_events collapsed dups
    assert horizon >= (cycles[-1] if cycles else 0)


def test_adversarial_events_deterministic():
    a, ha = adversarial_events(np.random.default_rng(42), n_events=30)
    b, hb = adversarial_events(np.random.default_rng(42), n_events=30)
    assert a == b and ha == hb


def test_differential_fuzz_200_programs():
    stats = differential_fuzz(200, seed=0)
    assert stats["programs"] == 200
    assert stats["mismatches"] == 0
    assert stats["runs"] == 400  # one per (program, hw_auto) pairing
    assert stats["events"] > 0 and stats["cycles"] > 0


def test_differential_fuzz_is_deterministic():
    a = differential_fuzz(10, seed=9)
    b = differential_fuzz(10, seed=9)
    assert a == b


def test_fuzz_detects_divergence():
    """The harness itself must fail loudly: corrupt one executor run
    by hand and check the mismatch formatter names the counter."""
    events, horizon = adversarial_events(np.random.default_rng(1))
    kw = dict(FUZZ_KW, hw_auto_gating=True,
              initial_modes=dict(FUZZ_KW["initial_modes"]))
    ref = VLIWTimeline(npu="NPU-D", **kw).run(
        expand_events(events, horizon))
    got = EventTimeline(npu="NPU-D", **kw).run(events, horizon=horizon)
    from repro.core.perturb import _exec_mismatch
    assert _exec_mismatch(ref, got) is None
    bad = dataclasses.replace(got, cycles=got.cycles + 1)
    assert "cycles" in _exec_mismatch(ref, bad)
