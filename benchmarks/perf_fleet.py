"""Fleet-plane throughput benchmark (ISSUE 7).

Simulates a 24h day on a 4096-chip fleet — 4 tenant classes, ~2.3M
requests, 96 serving epochs, a 24-point knob grid, 3 congestion levels —
and gates the one-batched-call-per-epoch design: ``sweep_fleet``'s
epoch rate must be >= 10x a per-cell reference that evaluates the same
epochs through an ``evaluate`` loop (one one-cell sweep-kernel call
on the host per (workload, policy, knob) cell). The reference only
replays ``REF_EPOCHS`` epochs — at per-cell speed the full day would
dominate CI — and is scaled to an epochs/sec rate on identical epoch
inputs (``keep_epoch_inputs``), so both sides price exactly the same
evaluation work.

Writes ``BENCH_fleet.json`` (registered in ``check_regression``).

  PYTHONPATH=src python -m benchmarks.perf_fleet [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import time

from repro.core.fleet import (ArrivalSpec, FleetScenario, WorkloadClass,
                              sweep_fleet)
from repro.core.hw import get_npu
from repro.core.opgen import dlrm_workload, llm_workload
from repro.core.policies import KnobGrid, evaluate

MIN_SPEEDUP = 10.0
MIN_REQUESTS = 1_000_000
REF_EPOCHS = 3

GRID = KnobGrid(window_scale=(0.25, 0.5, 1.0, 2.0),
                delay_scale=(1.0, 2.0, 4.0),
                leak_off_logic=(None, 0.2))


def build_scenario() -> FleetScenario:
    """The examples/fleet_day.py fleet: diurnal chat decode + prefill,
    a bursty 70B tier, steady DLRM — >=1M requests on 4096 chips."""
    classes = (
        WorkloadClass(
            "chat-decode",
            llm_workload("llama3-8b", "decode", batch=8),
            ArrivalSpec("diurnal", rate_rps=10.0, peak_frac=0.9,
                        period_s=86400.0, phase_s=-21600.0),
            requests_per_invocation=8),
        WorkloadClass(
            "chat-prefill",
            llm_workload("llama3-8b", "prefill", batch=1, seq=4096),
            ArrivalSpec("diurnal", rate_rps=10.0, peak_frac=0.9,
                        period_s=86400.0, phase_s=-21600.0)),
        WorkloadClass(
            "research-70b",
            llm_workload("llama3-70b", "decode", batch=4, n_chips=8,
                         tp=8),
            ArrivalSpec("bursty", rate_rps=1.5, burst_prob=0.15,
                        burst_factor=8.0),
            requests_per_invocation=4),
        WorkloadClass(
            "ranking-dlrm", dlrm_workload("M"),
            ArrivalSpec("poisson", rate_rps=3.0),
            requests_per_invocation=1024),
    )
    return FleetScenario(
        classes=classes, n_chips=4096, npu="NPU-D",
        policies=("NoPG", "ReGate-HW", "ReGate-Full"),
        duration_s=86400.0, epoch_s=900.0, slo_relax=1.2, seed=7,
        severity_levels=(0.0, 0.5, 1.0))


def run(out_path: str = "BENCH_fleet.json", reps: int = 3) -> dict:
    sc = build_scenario()
    knobs = tuple(GRID.product())

    # warm-up run: compiles/caches every trace variant, and captures
    # the epoch inputs the per-cell reference will replay
    warm = sweep_fleet(sc, GRID, keep_epoch_inputs=True)
    assert warm.requests_total >= MIN_REQUESTS
    assert warm.n_chips >= 4096

    t_fleet = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        rep = sweep_fleet(sc, GRID)
        t_fleet = min(t_fleet, time.perf_counter() - t0)
    assert rep.records == warm.records  # determinism, while we're here

    # per-cell reference on identical epoch inputs: one evaluate()
    # round-trip per (workload, policy, knob) cell, REF_EPOCHS epochs
    npu = get_npu(sc.npu)
    ref_inputs = warm.epoch_inputs[:REF_EPOCHS]
    t0 = time.perf_counter()
    cells = 0
    for wls, _sev in ref_inputs:
        for wl in wls:
            for policy in sc.policies:
                for k in knobs:
                    evaluate(wl, npu, policy, k)
                    cells += 1
    t_ref = time.perf_counter() - t0

    eps_fleet = warm.n_epochs / t_fleet
    eps_ref = len(ref_inputs) / t_ref
    result = {
        "n_chips": warm.n_chips,
        "classes": len(sc.classes),
        "policies": len(sc.policies),
        "knob_settings": len(knobs),
        "epochs": warm.n_epochs,
        "requests_total": warm.requests_total,
        "severity_levels": len(sc.severity_levels),
        "fleet_wall_s": round(t_fleet, 4),
        "ref_epochs": len(ref_inputs),
        "ref_cells": cells,
        "ref_wall_s": round(t_ref, 4),
        "epochs_per_sec_fleet": round(eps_fleet, 2),
        "epochs_per_sec_ref": round(eps_ref, 2),
        "requests_per_sec": round(warm.requests_total / t_fleet),
        "speedup": round(eps_fleet / eps_ref, 2),
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_fleet.json")
    args = ap.parse_args(argv)
    r = run(args.out)
    for k, v in r.items():
        print(f"{k}: {v}")
    ok = (r["speedup"] >= MIN_SPEEDUP
          and r["requests_total"] >= MIN_REQUESTS
          and r["n_chips"] >= 4096)
    print(f"gate(speedup>={MIN_SPEEDUP:g}x & requests>="
          f"{MIN_REQUESTS:,} & chips>=4096): {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
