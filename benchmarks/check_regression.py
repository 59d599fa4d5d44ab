"""Bench regression gate for CI.

Compares freshly produced ``BENCH_*.json`` artifacts against the
committed baselines and fails when a metric regresses by more than the
allowed fraction (default 30%) — the speedup gates stop being
upload-only artifacts and start failing PRs.

  python -m benchmarks.check_regression \
      BENCH_timeline_executor.json:BENCH_timeline_executor.new.json \
      BENCH_program_plane.json:BENCH_program_plane.new.json \
      [--metric speedup] [--max-regression 0.30]

Each positional argument is ``baseline:fresh``. With no positional
arguments the registered ``DEFAULT_PAIRS`` are checked (every
benchmark that commits a baseline registers itself there).
Improvements always pass; a missing baseline file is an error (commit
one with the PR that introduces the benchmark).
"""
from __future__ import annotations

import argparse
import json
import sys

# every committed BENCH_*.json baseline and its fresh CI counterpart;
# new benchmarks register here so `python -m benchmarks.check_regression`
# with no arguments covers the full set
DEFAULT_PAIRS = [
    "BENCH_timeline_executor.json:BENCH_timeline_executor.new.json",
    "BENCH_program_plane.json:BENCH_program_plane.new.json",
    "BENCH_sweep_multidevice.json:BENCH_sweep_multidevice.new.json",
    "BENCH_perturb.json:BENCH_perturb.new.json",
    "BENCH_fleet.json:BENCH_fleet.new.json",
    "BENCH_chaos.json:BENCH_chaos.new.json",
    "BENCH_guard.json:BENCH_guard.new.json",
]


def check_pair(baseline_path: str, fresh_path: str, metric: str,
               max_regression: float) -> tuple[bool, str]:
    with open(baseline_path) as f:
        base = json.load(f)
    with open(fresh_path) as f:
        fresh = json.load(f)
    if metric not in base or metric not in fresh:
        return False, (f"{baseline_path}: metric {metric!r} missing "
                       f"(baseline has it: {metric in base}, "
                       f"fresh has it: {metric in fresh})")
    b, n = float(base[metric]), float(fresh[metric])
    floor = b * (1.0 - max_regression)
    ok = n >= floor
    verdict = "OK" if ok else "REGRESSION"
    return ok, (f"{verdict}: {baseline_path} {metric} baseline={b:g} "
                f"fresh={n:g} floor={floor:g} "
                f"({(n / b - 1.0) * 100:+.1f}%)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("pairs", nargs="*", metavar="BASELINE:FRESH",
                    default=DEFAULT_PAIRS,
                    help="baseline and fresh JSON paths, colon-separated "
                         "(default: the registered DEFAULT_PAIRS)")
    ap.add_argument("--metric", default="speedup")
    ap.add_argument("--max-regression", type=float, default=0.30,
                    help="allowed fractional drop vs baseline")
    args = ap.parse_args(argv)
    failed = 0
    for pair in args.pairs:
        if ":" not in pair:
            print(f"bad pair (need BASELINE:FRESH): {pair}")
            failed += 1
            continue
        baseline, fresh = pair.split(":", 1)
        try:
            ok, msg = check_pair(baseline, fresh, args.metric,
                                 args.max_regression)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            ok, msg = False, f"{pair}: {type(e).__name__}: {e}"
        print(msg)
        failed += 0 if ok else 1
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
