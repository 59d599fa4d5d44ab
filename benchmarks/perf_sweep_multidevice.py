"""Multi-device shard_map sweep benchmark (ISSUE 5).

Runs a ≥100k-cell design-space grid — 16 synthetic 2.5k-op workloads ×
5 NPU generations × 5 policies × 256 knobs (8 delay scales × 4 SA
widths × 4 logic leakages × 2 SRAM sleeps; 32 unique (width, delay)
pairs) — through the jax sweep twice inside ONE subprocess running
under ``--xla_force_host_platform_device_count=8``:

* **1-device**: the plain jitted kernel (no mesh), steady state;
* **8-device**: the ``shard_map`` program on a ``("wl", "knob")``
  mesh — op columns sharded over ``wl`` (psum-completed segment sums),
  unique pairs + knob grid sharded over ``knob``.

Equivalence is a hard gate everywhere: an NPU × thinned-knob subsample
of the grid must match the kernel's numpy instantiation
record-for-record ≤1e-9.

The ≥2x speedup gate arms only when the host has at least one core per
virtual device (``os.cpu_count() >= 8``): 8 virtual CPU devices
time-slice the physical cores, so on the 2-core container this repo is
grown on the strong-scaling ceiling is cores/1 ≈ 2x *before* overhead
— the run still measures and records the scaling honestly
(``speedup_gate_armed: false`` in the JSON), and CI-class machines arm
the gate. ``check_regression.py`` tracks the recorded speedup against
the committed baseline either way, so a scaling regression on the same
machine class fails the PR.

  PYTHONPATH=src python -m benchmarks.perf_sweep_multidevice [--out P]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

RTOL = 1e-9
MIN_SPEEDUP = 2.0
N_DEVICES = 8

N_WORKLOADS = 16
OPS_PER_WORKLOAD = 2500
GRID = dict(
    delay_scale=(0.25, 0.5, 0.7, 1.0, 2.0, 4.0, 8.0, 16.0),
    sa_width=(None, 64, 256, 512),
    leak_off_logic=(0.01, 0.03, 0.1, 0.4),
    leak_sram_sleep=(0.1, 0.4),
)
EQUIV_SUBSAMPLE = 32  # every 32nd knob of the flat 256-point grid


def _max_rel_dev(ref: list[dict], bat: list[dict]) -> float:
    """Worst relative deviation over every numeric field of every
    record; raises if orderings or field sets differ."""
    assert len(ref) == len(bat), (len(ref), len(bat))
    worst = 0.0
    for a, b in zip(ref, bat):
        assert set(a) == set(b), set(a) ^ set(b)
        for k, va in a.items():
            vb = b[k]
            if isinstance(va, (str, type(None))) or k == "knob_idx":
                assert va == vb, (k, va, vb)
                continue
            worst = max(worst,
                        abs(va - vb) / max(1e-30, abs(va), abs(vb)))
    return worst


def _synth_suite():
    """Deterministic synthetic suite with a large stacked op axis (the
    ``wl``-sharding regime: tens of thousands of ops, modest W)."""
    import numpy as np

    from repro.core.opgen import Op, Workload
    rng = np.random.default_rng(42)
    wls = []
    for i in range(N_WORKLOADS):
        ops = []
        for j in range(OPS_PER_WORKLOAD):
            f = float(rng.uniform(1e9, 5e12)) if rng.random() < 0.5 else 0.0
            mm = (int(rng.integers(1, 4096)), int(rng.integers(1, 512)),
                  int(rng.integers(1, 4096))) if f else None
            ops.append(Op(
                f"op{j}", flops_sa=f,
                flops_vu=float(rng.uniform(1e8, 5e11))
                if rng.random() < 0.5 else 0.0,
                bytes_hbm=float(rng.uniform(1e6, 1e10))
                if rng.random() < 0.6 else 0.0,
                bytes_ici=float(rng.uniform(1e6, 1e9))
                if rng.random() < 0.15 else 0.0,
                sram_demand=int(rng.integers(0, 256 << 20)),
                matmul_dims=mm, count=int(rng.integers(1, 4))))
        wls.append(Workload(f"synth-{i}", "prefill", tuple(ops)))
    return wls


def _inner(out_path: str, reps: int) -> None:
    """Runs inside the 8-virtual-device subprocess."""
    import jax
    assert len(jax.devices()) == N_DEVICES, jax.devices()
    from repro.core.hw import NPUS
    from repro.core.policies import POLICIES, evaluate_batch
    from repro.core.sweep import knob_product, sweep
    from repro.parallel import jax_compat

    wls = _synth_suite()
    grid = knob_product(**GRID)
    npus = tuple(NPUS)
    n_cells = len(wls) * len(npus) * len(POLICIES) * len(grid)
    mesh = jax_compat.sweep_mesh(wl=4, knob=2)

    def run(m):
        return evaluate_batch(wls, npus, POLICIES, grid, backend="jax",
                              jax_mesh=m)

    # first calls compile; steady state reuses the programs
    t0 = time.perf_counter()
    run(None)
    compile_1dev = time.perf_counter() - t0
    t0 = time.perf_counter()
    run(mesh)
    compile_8dev = time.perf_counter() - t0
    t_1dev = t_8dev = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        res = run(None)
        t_1dev = min(t_1dev, time.perf_counter() - t0)
    assert res.shape == (len(wls), len(npus), len(POLICIES), len(grid))
    for _ in range(reps):
        t0 = time.perf_counter()
        run(mesh)
        t_8dev = min(t_8dev, time.perf_counter() - t0)

    # --- equivalence vs the numpy kernel on a thinned subsample ---
    sub = grid[::EQUIV_SUBSAMPLE]
    ref = sweep(wls, ("NPU-D",), POLICIES, sub, backend="numpy")
    got = evaluate_batch(wls, ("NPU-D",), POLICIES, sub, backend="jax",
                         jax_mesh=mesh).records()
    key = ("workload", "npu", "policy", "knob_idx")
    ordering_ok = [tuple(r[k] for k in key) for r in ref] \
        == [tuple(r[k] for k in key) for r in got]
    max_dev = _max_rel_dev(ref, got)

    host_cpus = os.cpu_count() or 1
    result = {
        "devices": N_DEVICES,
        "mesh": "wl=4 x knob=2",
        "host_cpus": host_cpus,
        "workloads": len(wls),
        "stacked_ops": sum(len(w.ops) for w in wls),
        "knob_settings": len(grid),
        "sweep_cells": n_cells,
        "equiv_cells": len(ref),
        "wall_1dev_s": round(t_1dev, 4),
        "wall_8dev_s": round(t_8dev, 4),
        "compile_1dev_s": round(compile_1dev - t_1dev, 4),
        "compile_8dev_s": round(compile_8dev - t_8dev, 4),
        "speedup": round(t_1dev / t_8dev, 3),
        "speedup_gate_armed": host_cpus >= N_DEVICES,
        "max_rel_dev": max_dev,
        "ordering_identical": ordering_ok,
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)


def run(out_path: str = "BENCH_sweep_multidevice.json",
        reps: int = 3) -> dict:
    # This measures CPU virtual devices only: the child is forced onto
    # the CPU platform. On a TPU host the meshes run in one process —
    # `python chip_smoke.py --chips 4` — since a chip belongs to the
    # one process that first touches it.
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{N_DEVICES}").strip()
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.perf_sweep_multidevice",
         "--inner", "--out", out_path, "--reps", str(reps)],
        env=env, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"inner benchmark failed ({r.returncode})")
    with open(out_path) as f:
        return json.load(f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_sweep_multidevice.json")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--inner", action="store_true")
    args = ap.parse_args(argv)
    if args.inner:
        _inner(args.out, args.reps)
        return 0
    r = run(args.out, args.reps)
    for k, v in r.items():
        print(f"{k}: {v}")
    equiv_ok = r["max_rel_dev"] <= RTOL and r["ordering_identical"]
    if r["speedup_gate_armed"]:
        ok = equiv_ok and r["speedup"] >= MIN_SPEEDUP
        print(f"gate(equiv<=1e-9 & speedup>={MIN_SPEEDUP:g}x on "
              f"{r['host_cpus']} cpus): {'PASS' if ok else 'FAIL'}")
    else:
        ok = equiv_ok
        print(f"gate(equiv<=1e-9; speedup gate unarmed — "
              f"{r['host_cpus']} cpus < {N_DEVICES} devices, scaling "
              f"recorded as {r['speedup']}x): "
              f"{'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
