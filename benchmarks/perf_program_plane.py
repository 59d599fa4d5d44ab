"""Batched program-plane sweep throughput benchmark (ISSUE 10).

Times ``sweep_program_plane`` — the full paper suite x two NPU
generations x a 4-point BET/window knob grid, executed through the
``repro.core.program_plane`` array kernel — against the per-cell host
oracle ``sweep_program_plane_reference`` (one ``EventTimeline`` run +
one scalar ``evaluate_reference`` per cell).

Records are compared cell-for-cell BEFORE timing counts: executor-side
fields (cycles, stalls, wakes, setpm counts) must match exactly,
everything else to <=1e-9 relative — a speedup over wrong answers is
not a speedup. The acceptance gate is speedup >= 10x on the best
backend (jax when available — the scan kernel jit-compiles once and is
reused; the numpy scan is also reported).

Writes ``BENCH_program_plane.json``; CI compares the committed baseline
against a fresh run via ``benchmarks.check_regression``.

  PYTHONPATH=src python -m benchmarks.perf_program_plane [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import time

from repro.core.opgen import paper_suite
from repro.core.policies import KnobGrid
from repro.core.sweep import (sweep_program_plane,
                              sweep_program_plane_reference)

SPEEDUP_GATE = 10.0
NPUS = ("NPU-B", "NPU-D")
# 4 BET/window points x 2 leak points: the executor re-runs only per
# unique (sa_width, delay_scale, window_scale) triple (leak knobs never
# move program-plane statistics), so the leak axis rides the batched
# path at near-zero marginal cost — the per-cell oracle pays full price
GRID = KnobGrid(delay_scale=(1.0, 4.0), window_scale=(1.0, 0.5),
                leak_off_logic=(None, 0.1))


def _check_records(got: list[dict], ref: list[dict]) -> None:
    assert len(got) == len(ref), (len(got), len(ref))
    for i, (x, y) in enumerate(zip(ref, got)):
        assert set(x) == set(y), i
        for k in x:
            a, b = x[k], y[k]
            if a is None or isinstance(a, str):
                assert a == b, (i, k, a, b)
            elif k.startswith(("prog_", "n_events", "stall_",
                               "wakes_prog", "setpm_prog")):
                assert float(a) == float(b), (i, k, a, b)
            else:
                assert abs(float(a) - float(b)) \
                    <= 1e-9 * max(1.0, abs(float(a))), (i, k, a, b)


def _time_best(fn, reps: int) -> tuple[float, list[dict]]:
    best, out = float("inf"), None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def run(out_path: str = "BENCH_program_plane.json", reps: int = 3) -> dict:
    wls = paper_suite()
    grid = tuple(GRID.product())

    def reference():
        return sweep_program_plane_reference(wls, npus=NPUS,
                                             knob_grid=grid)

    def batched(backend):
        return lambda: sweep_program_plane(wls, npus=NPUS, knob_grid=grid,
                                           backend=backend)

    # warm every path once: caches (lowering / instrumentation / event
    # streams) and the jit compile are one-time costs both sides share
    ref_recs = reference()
    backends = ["numpy"]
    try:
        import jax  # noqa: F401
        backends.append("jax")
    except ImportError:  # pragma: no cover - jax ships in CI
        pass
    wall = {}
    for b in backends:
        batched(b)()  # warm (jit compile on jax)
        wall[b], recs = _time_best(batched(b), reps)
        _check_records(recs, ref_recs)

    t_ref, _ = _time_best(reference, reps)
    best = min(backends, key=lambda b: wall[b])
    result = {
        "n_workloads": len(wls),
        "n_npus": len(NPUS),
        "n_knobs": len(grid),
        "n_cells": len(ref_recs),
        "reference_wall_s": round(t_ref, 4),
        "batched_wall_s_numpy": round(wall["numpy"], 4),
        **({"batched_wall_s_jax": round(wall["jax"], 4)}
           if "jax" in wall else {}),
        "best_backend": best,
        "cells_per_sec": round(len(ref_recs) / wall[best], 1),
        "speedup_numpy": round(t_ref / wall["numpy"], 2),
        "speedup": round(t_ref / wall[best], 2),
        "records_equal": True,
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_program_plane.json")
    args = ap.parse_args(argv)
    r = run(args.out)
    for k, v in r.items():
        print(f"{k}: {v}")
    ok = r["speedup"] >= SPEEDUP_GATE
    print(f"gate(speedup>={SPEEDUP_GATE:.0f}x): {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
