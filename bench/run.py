"""Run one cell of the chip benchmark and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, on the machine that holds the chips. It refuses (non-zero
exit, no result) unless jax's first device is a TPU and there are as
many as the cell asks for. ``setup_s`` runs from the start of this
process, before jax is imported. The last line of stdout is the result
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and ``check`` last: each number
compared with the reference beside its limit, also printed as the last
lines of stderr). JAX's persistent compile cache lives in the
checkout's ``.jax_cache``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT           # import ``bench.*``, never this directory
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    # the TPU runtime's logs stay in the checkout, not in /tmp
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(ROOT, "results", "bench", "tpu_logs"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from bench import harness, peaks
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        cells = {w["name"]: w for w in json.load(fh)["workloads"]}
    if args.workload not in cells:
        print(f"bench: no cell {args.workload!r}", file=sys.stderr)
        return 2
    devices = jax.devices()
    print(f"set-up: devices found at {time.perf_counter() - T_START:.2f} s",
          flush=True)
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, but jax's first device is platform "
              f"{devices[0].platform!r} ({devices[0].device_kind})",
              file=sys.stderr)
        return 2
    want = cells[args.workload]["chips"]
    if len(devices) < want:
        print(f"bench: cell {args.workload!r} needs {want} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks.peaks(devices[0].device_kind)
    from repro.parallel import jax_compat
    jax_compat.use_compile_cache()
    out = harness.run(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
