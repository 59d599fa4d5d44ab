"""Guard plane (ISSUE 9): watchdog/failover/quarantine + checkpoints.

Pins the guard-plane invariants that don't need a real SIGKILL (those
live in ``test_guard_resume.py`` / ``test_serve_signals.py``):

* guarded execution is a no-op on the clean path — a guarded
  ``sweep_fleet`` is bit-identical to a plain one and records zero
  escalations;
* campaign checkpoints resume bit-identically (in-process: truncate
  the snapshot ledger and re-run) and a finished run short-circuits
  to its stored final report;
* a checkpoint directory refuses a different campaign (named
  ``ValueError`` from the RunManifest);
* NaN/Inf cells are quarantined, re-evaluated per-cell on the numpy
  oracle, and patched record-for-record to ≤1e-9, with one named
  quarantine event per poisoned cell;
* a wedged backend trips the deadline watchdog and walks the failover
  ladder jax-mesh → jax → numpy in order, with the deterministic
  seeded backoff schedule;
* exhausting the ladder raises a named ``GuardError``.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.core.fleet import (ArrivalSpec, FleetReport, FleetScenario,
                              WorkloadClass, sweep_chaos, sweep_fleet)
from repro.core.guard import (CampaignCheckpoint, GuardError,
                              GuardPolicy, GuardReport, GuardedRunner,
                              RunManifest, _GUARD_PLANE, digest_of)
from repro.core.opgen import llm_workload
from repro.core.policies import KnobGrid, PolicyKnobs, evaluate_batch
from repro.core.session import SweepSession

RTOL = 1e-9

GRID = KnobGrid(window_scale=(0.5, 1.0))

WL = llm_workload("llama3-8b", "decode", batch=8, n_chips=8, tp=8)


def _scenario(seed=11, **kw):
    base = dict(
        classes=(WorkloadClass(
            "decode", WL,
            ArrivalSpec("diurnal", rate_rps=12.0, period_s=1800.0),
            requests_per_invocation=8),),
        n_chips=16, npu="NPU-D", policies=("NoPG", "ReGate-Full"),
        duration_s=1800.0, epoch_s=600.0, seed=seed,
        severity_levels=(0.0, 1.0))
    base.update(kw)
    return FleetScenario(**base)


def _core(report: FleetReport) -> str:
    """The result payload (everything except guard bookkeeping),
    canonically serialized for bit-identity comparison."""
    d = report.to_dict()
    d.pop("guard")
    return json.dumps(d, sort_keys=True)


# --------------------------------------------------------------------------
# clean path: the guard never changes what is computed
# --------------------------------------------------------------------------

def test_guarded_fleet_matches_plain():
    sc = _scenario()
    plain = sweep_fleet(sc, GRID)
    guarded = sweep_fleet(sc, GRID, guard=GuardPolicy(timeout_s=300.0))
    assert _core(plain) == _core(guarded)
    assert plain.guard is None
    assert guarded.guard is not None and guarded.guard["events"] == []


def test_session_scopes_guard():
    sc = _scenario()
    with SweepSession(guard=GuardPolicy(timeout_s=300.0)):
        rep = sweep_fleet(sc, GRID)
    assert rep.guard is not None and rep.guard["events"] == []
    assert sweep_fleet(sc, GRID).guard is None   # scope ended


# --------------------------------------------------------------------------
# campaign checkpoints: resume + short-circuit + identity pinning
# --------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_resume(tmp_path):
    sc = _scenario()
    ref = sweep_fleet(sc, GRID, guard=GuardPolicy())  # no checkpoint

    full = sweep_fleet(sc, GRID, checkpoint=str(tmp_path / "a"))
    assert _core(full) == _core(ref)

    # finished run: a re-invocation short-circuits to final.json
    again = sweep_fleet(sc, GRID, checkpoint=str(tmp_path / "a"))
    assert json.dumps(again.to_dict(), sort_keys=True) \
        == json.dumps(full.to_dict(), sort_keys=True)

    # partial run: drop final.json + the newest snapshot, resume from
    # the surviving one — the replay must be bit-identical
    ckdir = tmp_path / "b"
    sweep_fleet(sc, GRID, checkpoint=str(ckdir))
    epochs = sorted(int(p.stem.split("_")[1])
                    for p in ckdir.glob("epoch_*.json"))
    assert len(epochs) == 2   # keep=2 retention
    (ckdir / "final.json").unlink()
    (ckdir / f"epoch_{epochs[-1]}.json").unlink()
    resumed = sweep_fleet(sc, GRID, checkpoint=str(ckdir))
    assert _core(resumed) == _core(ref)


def test_checkpoint_refuses_different_campaign(tmp_path):
    ckdir = str(tmp_path / "ck")
    sweep_fleet(_scenario(seed=11), GRID, checkpoint=ckdir)
    with pytest.raises(ValueError,
                       match="manifest mismatch on seed"):
        sweep_fleet(_scenario(seed=12), GRID, checkpoint=ckdir)
    with pytest.raises(ValueError,
                       match="manifest mismatch on knob_digest"):
        sweep_fleet(_scenario(seed=11),
                    KnobGrid(window_scale=(0.5, 2.0)),
                    checkpoint=ckdir)


def test_chaos_checkpoint_matches_plain(tmp_path):
    sc = _scenario()
    ref = sweep_chaos(sc, GRID, fault_severities=(0.0, 1.0),
                      thrash_baseline=False)
    out = sweep_chaos(sc, GRID, fault_severities=(0.0, 1.0),
                      thrash_baseline=False,
                      checkpoint=str(tmp_path / "c"))
    assert json.dumps(out["summary"], sort_keys=True) \
        == json.dumps(ref["summary"], sort_keys=True)
    for sev in (0.0, 1.0):
        assert _core(out["reports"][sev]) == _core(ref["reports"][sev])


def test_manifest_named_mismatch():
    kw = dict(kind="fleet", seed=1, n_epochs=3, backend="numpy",
              knob_digest="k", scenario_digest="s")
    a = RunManifest(**kw)
    b = RunManifest(**{**kw, "backend": "jax"})
    with pytest.raises(ValueError, match="mismatch on backend"):
        a.check(b)
    a.check(RunManifest(**kw))   # identical manifests pass


def test_digest_of_is_stable_and_sensitive():
    g = KnobGrid(window_scale=(0.5, 1.0))
    assert digest_of(g) == digest_of(KnobGrid(window_scale=(0.5, 1.0)))
    assert digest_of(g) != digest_of(KnobGrid(window_scale=(0.5, 2.0)))
    assert digest_of(np.arange(3)) != digest_of(np.arange(3.0))


# --------------------------------------------------------------------------
# quarantine: poisoned cells, oracle re-evaluation
# --------------------------------------------------------------------------

NPUS = ("NPU-D",)
POLS = ("NoPG", "ReGate-Full")
KNOBS = (PolicyKnobs(), PolicyKnobs(window_scale=2.0))


def _poisoning_runner(rung, workloads, npus, policies, knobs, *,
                      jax_mesh=None):
    """A backend whose cube comes back with a NaN and an Inf cell."""
    res = evaluate_batch(workloads, npus, policies, knobs,
                         backend="numpy")
    rt = res.runtime_s.copy()
    rt[0, 0, 0, 0] = np.nan
    sj = {c: a.copy() for c, a in res.static_j.items()}
    sj["sa"][-1, 0, -1, -1] = np.inf
    return dataclasses.replace(res, runtime_s=rt, static_j=sj)


def test_quarantine_patches_to_oracle():
    wls = [WL, llm_workload("llama3-8b", "prefill", batch=4, n_chips=8,
                            tp=8)]
    runner = GuardedRunner(GuardPolicy(), rungs=[("jax", None)],
                           runner=_poisoning_runner, seed=5)
    got = runner.evaluate_batch(wls, NPUS, POLS, KNOBS, step=3)
    ref = evaluate_batch(wls, NPUS, POLS, KNOBS, backend="numpy")

    # patched record-for-record to the oracle, ≤1e-9 everywhere
    for (name, a), (_, b) in zip(
            _fields(got), _fields(ref)):
        assert np.isfinite(a).all(), name
        err = np.abs(a - b) / np.maximum(np.abs(b), 1e-300)
        assert float(err.max()) <= RTOL, name

    evs = runner.report.events
    q = [e for e in evs if e["kind"] == "quarantine"]
    assert runner.report.quarantined_cells == len(q) == 2
    assert sorted(e["cell"] for e in q) == [[0, 0, 0, 0], [1, 0, 1, 1]]
    assert q[0]["fields"] == ["runtime_s"] and q[0]["step"] == 3
    assert "non-finite runtime_s" in q[0]["reason"]
    assert "numpy backend" in q[0]["reason"]
    assert q[1]["fields"] == ["static_j[sa]"]
    assert [e["kind"] for e in evs][-1] == "oracle_recheck"
    assert evs[-1]["n_quarantined"] == 2


def _fields(res):
    from repro.core.guard import _result_fields
    return _result_fields(res)


def test_quarantine_rejects_poisoned_oracle():
    def bad_oracle(workloads, npus, policies, knobs):
        return _poisoning_runner("x", workloads, npus, policies, knobs)

    runner = GuardedRunner(GuardPolicy(), rungs=[("jax", None)],
                           runner=_poisoning_runner, oracle=bad_oracle)
    with pytest.raises(GuardError, match="the model, not the backend"):
        runner.evaluate_batch([WL], NPUS, POLS, KNOBS)


def test_quarantine_rejects_untrustworthy_survivors():
    def skewed(rung, workloads, npus, policies, knobs, *, jax_mesh=None):
        res = _poisoning_runner(rung, workloads, npus, policies, knobs)
        return dataclasses.replace(res, runtime_s=res.runtime_s * 1.5)

    runner = GuardedRunner(GuardPolicy(), rungs=[("jax", None)],
                           runner=skewed)
    with pytest.raises(GuardError, match="beyond 1e-09"):
        runner.evaluate_batch([WL], NPUS, POLS, KNOBS)


# --------------------------------------------------------------------------
# watchdog + failover ladder + deterministic backoff
# --------------------------------------------------------------------------

def test_watchdog_walks_the_ladder():
    import time as _time
    calls = []

    def slow(rung, workloads, npus, policies, knobs, *, jax_mesh=None):
        calls.append(rung)
        if rung != "numpy":
            _time.sleep(10.0)   # wedged; abandoned by the watchdog
        return evaluate_batch(workloads, npus, policies, knobs,
                              backend="numpy")

    pol = GuardPolicy(timeout_s=0.05, max_retries=1,
                      backoff_base_s=0.001, backoff_factor=2.0,
                      backoff_jitter=0.1)
    runner = GuardedRunner(
        pol, rungs=[("jax-mesh", "MESH"), ("jax", None),
                    ("numpy", None)],
        runner=slow, seed=7)
    got = runner.evaluate_batch([WL], NPUS, POLS, KNOBS, step=2)
    ref = evaluate_batch([WL], NPUS, POLS, KNOBS, backend="numpy")
    assert float(np.max(np.abs(got.runtime_s - ref.runtime_s))) == 0.0

    assert calls == ["jax-mesh", "jax-mesh", "jax", "jax", "numpy"]
    kinds = [e["kind"] for e in runner.report.events]
    assert kinds == ["retry", "failover", "retry", "failover"]
    fo = [e for e in runner.report.events if e["kind"] == "failover"]
    assert (fo[0]["rung"], fo[0]["next_rung"]) == ("jax-mesh", "jax")
    assert (fo[1]["rung"], fo[1]["next_rung"]) == ("jax", "numpy")
    assert all("timeout" in e["reason"] for e in runner.report.events
               if e["kind"] == "retry")
    assert "exhausted after 2 attempts" in fo[0]["reason"]

    # the backoff schedule is the seeded guard stream, exactly
    rng = np.random.default_rng((7, _GUARD_PLANE, 2))
    expect = [pol.backoff_delay(0, rng), pol.backoff_delay(0, rng)]
    got_delays = [e["delay_s"] for e in runner.report.events
                  if e["kind"] == "retry"]
    assert got_delays == expect
    assert all(pol.backoff_base_s <= d
               <= pol.backoff_base_s * (1 + pol.backoff_jitter)
               for d in got_delays)


def test_ladder_exhaustion_raises_named_guard_error():
    def broken(rung, workloads, npus, policies, knobs, *, jax_mesh=None):
        raise RuntimeError("device lost")

    runner = GuardedRunner(
        GuardPolicy(max_retries=0, backoff_base_s=0.001),
        rungs=[("jax", None), ("numpy", None)], runner=broken)
    with pytest.raises(GuardError,
                       match="all 2 backend rungs exhausted"):
        runner.evaluate_batch([WL], NPUS, POLS, KNOBS, step=1)
    assert [e["kind"] for e in runner.report.events] == ["failover"]
    assert "device lost" in runner.report.events[0]["reason"]


def test_retry_recovers_without_failover():
    state = {"n": 0}

    def flaky(rung, workloads, npus, policies, knobs, *, jax_mesh=None):
        state["n"] += 1
        if state["n"] == 1:
            raise RuntimeError("transient")
        return evaluate_batch(workloads, npus, policies, knobs,
                              backend="numpy")

    runner = GuardedRunner(
        GuardPolicy(max_retries=2, backoff_base_s=0.001),
        rungs=[("jax", None), ("numpy", None)], runner=flaky)
    runner.evaluate_batch([WL], NPUS, POLS, KNOBS)
    assert runner.report.retries == 1
    assert runner.report.failovers == 0


# --------------------------------------------------------------------------
# report + checkpoint plumbing
# --------------------------------------------------------------------------

def test_guard_report_roundtrip():
    r = GuardReport()
    r.add("retry", "timeout: deadline 0.05s exceeded", step=1,
          delay_s=0.0011)
    r.add("quarantine", "non-finite runtime_s", cell=[0, 0, 0, 0])
    d = r.to_dict()
    assert d["retries"] == 1 and d["quarantined_cells"] == 1
    back = GuardReport.from_dict(json.loads(json.dumps(d)))
    assert back.events == r.events


def test_campaign_checkpoint_gc_and_async_wait(tmp_path):
    m = RunManifest(kind="fleet", seed=0, n_epochs=10, backend="numpy",
                    knob_digest="k", scenario_digest="s")
    ck = CampaignCheckpoint(tmp_path / "ck", m, keep=2)
    for e in range(5):
        ck.save_epoch(e, {"epoch": e, "payload": [e] * 3})
    ck.wait()
    assert ck.epochs() == [3, 4]
    assert ck.load_epoch() == {"epoch": 4, "payload": [4, 4, 4]}
    assert ck.load_final() is None
    ck.save_final({"done": True})
    assert ck.load_final() == {"done": True}
    # a second handle over the same directory accepts the manifest
    CampaignCheckpoint(tmp_path / "ck", m, keep=2)
