"""Idle-detection robustness sweep (jitter plane, ISSUE 6).

Structure and invariants of ``sweep.sweep_robustness`` — record/summary
shape, severity-0 null behavior, deployed/chosen threshold flags,
SLO-constrained regret activation under heavy jitter — plus unit tests
for ``slo.runtime_violation_rate`` and the jax-backend path.
"""
import numpy as np
import pytest

from repro.core.opgen import llm_workload, paper_suite
from repro.core.slo import runtime_violation_rate
from repro.core.sweep import sweep_robustness

WLS = paper_suite()[10:12]          # llama3-70b / llama3.1-405b decode
SEVS = (0.0, 1.0, 2.0)
TS = (0.25, 1.0, 4.0)


@pytest.fixture(scope="module")
def out():
    return sweep_robustness(WLS, severities=SEVS, threshold_scales=TS,
                            seed=0)


def test_output_structure(out):
    assert set(out) == {"records", "summary", "severities",
                        "threshold_scales"}
    assert out["severities"] == list(SEVS)
    assert out["threshold_scales"] == list(TS)
    # one summary row per (npu, policy, severity); one record per cell
    assert len(out["summary"]) == 1 * 1 * len(SEVS)
    assert len(out["records"]) == len(WLS) * 1 * 1 * len(SEVS) * len(TS)
    r = out["records"][0]
    # since ISSUE 7 every sweep record carries the full knob-column set
    # (KnobGrid.columns() + knob_idx) unconditionally
    from repro.core.policies import KnobGrid
    assert set(r) == {"workload", "npu", "policy", "severity",
                      "knob_idx", *KnobGrid.columns(),
                      "runtime_s", "total_j",
                      "exposed_wake_s", "deployed", "chosen"}
    assert r["knob_idx"] == 0 and r["delay_scale"] == 1.0
    assert r["window_scale"] == TS[0]


def test_severity_zero_is_null(out):
    s0 = next(s for s in out["summary"] if s["severity"] == 0.0)
    assert s0["slo_violation_rate"] == 0.0
    assert s0["max_regret_frac"] == 0.0
    assert s0["mean_regret_frac"] == 0.0


def test_records_finite_and_nonnegative(out):
    for r in out["records"]:
        assert np.isfinite(r["runtime_s"]) and r["runtime_s"] > 0
        assert np.isfinite(r["total_j"]) and r["total_j"] > 0
        assert r["exposed_wake_s"] >= 0.0
    for s in out["summary"]:
        assert s["worst_exposed_wake_s"] >= 0.0
        assert s["worst_exposed_wake_any_s"] >= s["worst_exposed_wake_s"]
        assert 0.0 <= s["slo_violation_rate"] <= 1.0
        assert s["max_regret_frac"] >= s["mean_regret_frac"] >= 0.0


def test_deployed_and_chosen_flags(out):
    """Exactly one deployed and one chosen threshold per (workload,
    severity) group; at severity 0 they coincide (nothing violates)."""
    groups = {}
    for r in out["records"]:
        groups.setdefault((r["workload"], r["severity"]), []).append(r)
    assert len(groups) == len(WLS) * len(SEVS)
    for (wl, sev), rows in groups.items():
        assert sum(r["deployed"] for r in rows) == 1
        assert sum(r["chosen"] for r in rows) == 1
        dep = next(r for r in rows if r["deployed"])
        if sev == 0.0:
            assert dep["chosen"]
        # the deployed threshold is the same at every severity
        assert dep["window_scale"] == next(
            r for r in groups[(wl, 0.0)] if r["deployed"])["window_scale"]


def test_regret_activates_under_heavy_jitter(out):
    """The paper-level story: the clean-tuned (most aggressive)
    threshold blows the 1.1x SLO once jitter fragments the idle
    intervals, and re-tuning to a feasible threshold costs energy."""
    s2 = next(s for s in out["summary"] if s["severity"] == 2.0)
    assert s2["slo_violation_rate"] > 0.0
    assert s2["max_regret_frac"] > 0.0
    s0 = next(s for s in out["summary"] if s["severity"] == 0.0)
    assert s2["worst_exposed_wake_s"] > s0["worst_exposed_wake_s"]
    # re-tuning moved the chosen threshold off the deployed one
    moved = [r for r in out["records"]
             if r["severity"] == 2.0 and r["chosen"] and not r["deployed"]]
    assert moved


def test_single_workload_and_no_topology():
    wl = llm_workload("llama3-8b", "decode", batch=8, n_chips=8,
                      tp=8, dp=1)
    out = sweep_robustness(wl, severities=(0.0,), threshold_scales=(1.0,),
                           topology=False)
    assert len(out["records"]) == 1
    assert out["records"][0]["workload"] == wl.name


def test_threshold_scales_validated():
    for bad in ((0.0,), (-1.0,), (float("nan"),)):
        with pytest.raises(ValueError, match="threshold_scales"):
            sweep_robustness(WLS, severities=(0.0,),
                             threshold_scales=bad)


def test_jax_backend_matches_numpy(out):
    pytest.importorskip("jax")
    oj = sweep_robustness(WLS, severities=SEVS, threshold_scales=TS,
                          seed=0, backend="jax")
    assert len(oj["records"]) == len(out["records"])
    for a, b in zip(out["records"], oj["records"]):
        for k in ("workload", "severity", "window_scale", "deployed",
                  "chosen"):
            assert a[k] == b[k]
        for k in ("runtime_s", "total_j", "exposed_wake_s"):
            assert np.isclose(a[k], b[k], rtol=1e-9, atol=1e-12), (a, k)
    for a, b in zip(out["summary"], oj["summary"]):
        for k, v in a.items():
            if isinstance(v, float):
                assert np.isclose(v, b[k], rtol=1e-9, atol=1e-12), k
            else:
                assert v == b[k]


# --------------------------------------------------- runtime_violation_rate

def test_violation_rate_math():
    r = np.array([1.0, 1.2, 2.0, 1.05])
    b = np.ones(4)
    assert runtime_violation_rate(r, b, slo_relax=1.1) == 0.5
    assert runtime_violation_rate(r, b, slo_relax=2.5) == 0.0
    assert runtime_violation_rate(r, b, slo_relax=0.5) == 1.0


def test_violation_rate_edge_cases():
    assert runtime_violation_rate([], []) == 0.0
    with pytest.raises(ValueError):
        runtime_violation_rate([1.0], [1.0], slo_relax=0.0)
    with pytest.raises(ValueError):
        runtime_violation_rate([1.0, 2.0], [1.0])
