"""The control of the comparison: the reference put in the program's
place, computed in float32 (the precision below the simulator's
float64), has to come out as not correct.

  python3 bench/control.py --workload <cell> --seeds 1 2 3 [--queries 8]

For each seed it draws the queries a run's window would send and keeps
the same records a run keeps, computed by the float32 reference, then
compares a sample of them with the float64 reference exactly as a run
does. Prints one JSON line per seed with both compared numbers beside
their limits. Needs no chip and no program.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[0] = ROOT


def control(root: str, cell: str, seed: int, n_queries: int) -> dict:
    from bench import generator, harness
    _bench, _cell, config, traffic = harness.load_cell(root, cell)
    entry_mod = importlib.import_module(f"bench.entries.{traffic['entry']}")
    first = harness.WARMUP_QUERIES
    keep = traffic["check"]["kept_per_query"]
    kept = []
    for i in range(first, first + n_queries):
        q = generator.query(config, traffic, seed, i)
        n = entry_mod.Entry.size(q)
        idx = harness.kept_indices(seed, q["index"], n, keep)
        recs = entry_mod.reference(config, q, idx, np.float32)
        kept.append((q, n, list(zip(idx, recs))))
    dev = harness.check(entry_mod, config, kept, seed,
                        traffic["check"]["sample"])
    limits = {"max_rel_dev": entry_mod.LIMIT, "mismatches": 0}
    correct = all(dev[k] <= v for k, v in limits.items())
    return {"cell": cell, "seed": seed, "correct": correct,
            "check": {k: {"value": dev[k], "limit": v}
                      for k, v in limits.items()},
            "compared": dev["compared"], "first": dev["first"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--queries", type=int, default=24)
    args = ap.parse_args(argv)
    for s in args.seeds:
        print(json.dumps(control(ROOT, args.workload, s, args.queries)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
