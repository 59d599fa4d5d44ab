"""Benchmark harness: one function per paper table/figure plus the
dry-run roofline table. Prints ``name,value,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--only fig17,fig19] [--list]
                                          [--json BENCH_figures.json]
                                          [--backend numpy|jax]

``--json`` additionally writes a machine-readable artifact with every
row plus per-benchmark wall times, so the perf trajectory of the
simulator itself lands in version-controlled ``BENCH_*.json`` files.
``--backend`` scopes the whole run inside a
``repro.core.session.SweepSession`` so every batched sweep a figure
runs — without threading a flag through each function — executes on the
chosen substrate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated substring filters")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write rows + timings to this JSON file")
    ap.add_argument("--backend", default=None, choices=("numpy", "jax"),
                    help="session array backend for all sweeps")
    args = ap.parse_args(argv)

    from repro.parallel.jax_compat import use_compile_cache
    use_compile_cache()
    if args.backend:
        from repro.core.session import SweepSession
        with SweepSession(backend=args.backend):
            return _run(args)
    return _run(args)


def _run(args) -> int:
    from benchmarks.figures import REGISTRY
    from benchmarks import arch_power, roofline_table

    benches = dict(REGISTRY)
    benches["roofline_table"] = roofline_table.main
    benches["arch_power_table"] = arch_power.arch_power_table
    benches["regate_on_dryrun_cells"] = arch_power.regate_on_dryrun_cells

    if args.list:
        for name in benches:
            print(name)
        return 0

    filters = args.only.split(",") if args.only else None
    print("name,value,derived")
    failures = 0
    t_start = time.time()
    artifact: dict = {"benchmarks": {}, "errors": {}}
    for name, fn in benches.items():
        if filters and not any(f in name for f in filters):
            continue
        t0 = time.time()
        try:
            rows = []
            for row in fn():
                key, val, note = (list(row) + ["", ""])[:3]
                print(f"{key},{val},{note}")
                rows.append({"name": key, "value": val, "note": note})
            dt = time.time() - t0
            print(f"_timing/{name},{dt:.2f}s,")
            artifact["benchmarks"][name] = {"wall_s": round(dt, 4),
                                            "rows": rows}
        except Exception as e:  # noqa
            failures += 1
            print(f"_error/{name},{type(e).__name__}: {e},")
            artifact["errors"][name] = f"{type(e).__name__}: {e}"
    artifact["total_wall_s"] = round(time.time() - t_start, 4)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"_json/{args.json},written,")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
