"""Equivalence of the policy engine against the scalar oracle.

``evaluate`` — one cell of the sweep kernel's numpy instantiation —
must reproduce the oracle ``evaluate_reference`` to <=1e-9 relative on
every EnergyReport field, across the full paper suite x all 5 policies
x all NPU generations (plus knob overrides), and the batched SA-gating
math must match its scalar originals on randomized shapes.
"""
import math

import numpy as np
import pytest

from repro.core.hw import NPUS, get_npu
from repro.core.opgen import compile_trace, llm_workload, paper_suite
from repro.core.policies import (POLICIES, PolicyKnobs, evaluate,
                                 evaluate_reference, trace_times)
from repro.core.power import COMPONENTS
from repro.core.sa_gating import (gating_stats, gating_stats_batch,
                                  simulate_pe_grid,
                                  simulate_pe_grid_reference)
from repro.core.sweep import group_by, sweep, with_savings

RTOL = 1e-9


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(1e-30, abs(a), abs(b))


def _assert_reports_match(a, b, ctx: str):
    assert _rel(a.runtime_s, b.runtime_s) <= RTOL, (ctx, "runtime")
    assert _rel(a.total_j, b.total_j) <= RTOL, (ctx, "total_j")
    assert _rel(a.setpm_count, b.setpm_count) <= RTOL, (ctx, "setpm")
    for c in COMPONENTS:
        assert _rel(a.static_j[c], b.static_j[c]) <= RTOL, (ctx, c)
        assert _rel(a.dynamic_j[c], b.dynamic_j[c]) <= RTOL, (ctx, c)
        assert _rel(a.wake_events[c], b.wake_events[c]) <= RTOL, (ctx, c)
        assert _rel(a.gated_s[c], b.gated_s[c]) <= RTOL, (ctx, c, "gated")
        assert _rel(a.setpm_by[c], b.setpm_by[c]) <= RTOL, \
            (ctx, c, "setpm_by")


@pytest.mark.parametrize("npu", sorted(NPUS))
@pytest.mark.parametrize("policy", POLICIES)
def test_vectorized_matches_reference_full_suite(npu, policy):
    for wl in paper_suite():
        _assert_reports_match(evaluate(wl, npu, policy),
                              evaluate_reference(wl, npu, policy),
                              f"{wl.name}/{policy}/{npu}")


@pytest.mark.parametrize("knobs", [
    PolicyKnobs(delay_scale=0.5),
    PolicyKnobs(delay_scale=4.0),
    PolicyKnobs(leak_off_logic=0.2, leak_sram_sleep=0.4,
                leak_sram_off=0.02),
    PolicyKnobs(leak_off_logic=0.0, delay_scale=2.0),
])
def test_vectorized_matches_reference_knob_overrides(knobs):
    for wl in paper_suite()[::4]:
        for policy in POLICIES:
            _assert_reports_match(
                evaluate(wl, "NPU-D", policy, knobs),
                evaluate_reference(wl, "NPU-D", policy, knobs),
                f"{wl.name}/{policy}/{knobs}")


def test_gating_stats_batch_matches_scalar_randomized():
    rng = np.random.default_rng(0)
    Ms = np.concatenate([rng.integers(1, 5000, 200), [1, 1, 8, 131072]])
    Ks = np.concatenate([rng.integers(1, 3000, 200), [1, 128, 64, 16384]])
    Ns = np.concatenate([rng.integers(1, 3000, 200), [1, 128, 129, 8016]])
    for saw in (8, 128, 256):
        batch = gating_stats_batch(Ms, Ks, Ns, saw)
        for i, (M, K, N) in enumerate(zip(Ms, Ks, Ns)):
            st = gating_stats(int(M), int(K), int(N), saw)
            assert math.isclose(batch.duration_cycles[i],
                                st.duration_cycles, rel_tol=RTOL)
            assert math.isclose(batch.frac_on[i], st.frac_on, rel_tol=RTOL)
            assert math.isclose(batch.frac_w_on[i], st.frac_w_on,
                                rel_tol=RTOL, abs_tol=1e-15)
            assert math.isclose(batch.frac_off[i], st.frac_off,
                                rel_tol=RTOL, abs_tol=1e-15)
            assert batch.wake_events[i] == st.wake_events


def test_simulate_pe_grid_matches_reference_randomized():
    rng = np.random.default_rng(1)
    for _ in range(25):
        saw = int(rng.choice([4, 8, 12]))
        M = int(rng.integers(1, 30))
        K = int(rng.integers(1, saw + 1))
        N = int(rng.integers(1, saw + 1))
        assert simulate_pe_grid(M, K, N, saw) \
            == simulate_pe_grid_reference(M, K, N, saw)


def test_simulate_pe_grid_vectorized_large_grid():
    """saw=128 is infeasible for the triple loop but cheap vectorized;
    cross-check against the closed form instead."""
    sim = simulate_pe_grid(512, 100, 64, 128)
    st = gating_stats(512, 100, 64, 128, weight_load_cycles=0)
    tot = sim["total"]
    assert math.isclose(st.frac_on, sim["on"] / tot, rel_tol=RTOL)
    assert math.isclose(st.frac_w_on, sim["w_on"] / tot, rel_tol=RTOL)
    assert math.isclose(st.frac_off, sim["off"] / tot, rel_tol=RTOL)


def test_compile_trace_columnar_totals():
    wl = llm_workload("llama3-8b", "decode", batch=8, n_chips=1)
    tr = compile_trace(wl)
    assert tr.n_ops == len(wl.ops)
    for attr in ("flops_sa", "flops_vu", "bytes_hbm", "bytes_ici"):
        assert math.isclose(tr.total(attr), wl.total(attr), rel_tol=RTOL)
    assert tr.n_instances == sum(o.count for o in wl.ops)
    # identity cache: same workload object -> same trace object
    assert compile_trace(wl) is tr
    # matmul dims round-trip
    for i, op in enumerate(wl.ops):
        if op.matmul_dims is not None:
            assert tr.has_mm[i]
            assert (tr.mm_m[i], tr.mm_k[i], tr.mm_n[i]) == op.matmul_dims
        else:
            assert not tr.has_mm[i]


def test_trace_times_cached_per_npu():
    wl = llm_workload("llama3-8b", "prefill", batch=4, n_chips=1)
    tr = compile_trace(wl)
    tm_d = trace_times(tr, get_npu("NPU-D"))
    assert trace_times(tr, get_npu("NPU-D")) is tm_d
    tm_e = trace_times(tr, get_npu("NPU-E"))
    assert tm_e is not tm_d


def test_trace_times_not_stale_for_modified_spec():
    """A replace()-modified spec reusing a registry name must not hit the
    registry spec's cached times (what-if exploration)."""
    from dataclasses import replace
    wl = llm_workload("llama3-8b", "prefill", batch=4, n_chips=1)
    base = get_npu("NPU-D")
    evaluate(wl, base, "NoPG")  # warm the cache for the registry spec
    fat = replace(base, sa_width=256)
    _assert_reports_match(evaluate(wl, fat, "NoPG"),
                          evaluate_reference(wl, fat, "NoPG"),
                          "modified-spec")


def test_sweep_records_match_direct_evaluate():
    wls = paper_suite()[:2]
    recs = with_savings(sweep(wls, npus=("NPU-D",), policies=POLICIES))
    assert len(recs) == len(wls) * len(POLICIES)
    by_cell = {(r["workload"], r["policy"]): r for r in recs}
    for wl in wls:
        base = evaluate(wl, "NPU-D", "NoPG")
        for p in POLICIES:
            rep = evaluate(wl, "NPU-D", p)
            r = by_cell[(wl.name, p)]
            assert _rel(r["total_j"], rep.total_j) <= RTOL
            assert _rel(r["runtime_s"], rep.runtime_s) <= RTOL
            assert math.isclose(r["savings"],
                                1.0 - rep.total_j / base.total_j,
                                rel_tol=RTOL, abs_tol=1e-12)
        grp = group_by([r for r in recs if r["workload"] == wl.name],
                       "policy")
        assert set(grp) == {(p,) for p in POLICIES}


def test_sweep_knob_grid_ordering():
    grid = [PolicyKnobs(), PolicyKnobs(delay_scale=2.0)]
    recs = sweep(paper_suite()[0], npus=("NPU-A", "NPU-D"),
                 policies=("NoPG", "ReGate-Full"), knob_grid=grid)
    assert len(recs) == 2 * 2 * 2
    # deterministic order: npu-major, then policy, then knob index
    assert [(r["npu"], r["policy"], r["knob_idx"]) for r in recs] == [
        (n, p, k) for n in ("NPU-A", "NPU-D")
        for p in ("NoPG", "ReGate-Full") for k in (0, 1)]
    assert recs[1]["delay_scale"] == 2.0
