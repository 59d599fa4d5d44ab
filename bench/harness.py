"""One run of one benchmark cell, driven by the files the cell names.

``run`` finds everything by name: the cell in ``BENCHMARK.json``, its
configuration file, its traffic file under ``bench/traffic``, the entry
module under ``bench/entries`` that the traffic file names, and one
reader per per-layer metric under ``bench/metrics``. Nothing here names
a cell, a configuration or a traffic mix.

A run: set up (the entry's program objects, then ``WARMUP_QUERIES``
queries of the cell's shapes, so every program is compiled or loaded
from the persistent cache), then a closed loop of one client that sends
query after query for ``seconds`` (or, traced, a few queries under the
profiler), then the comparison of a sample of the window's records with
the plain reference. Each query is wrapped in the host annotations
``bench.query`` > ``bench.opgen`` / ``bench.call`` / ``bench.records``.
"""
from __future__ import annotations

import glob
import importlib
import json
import os
import shutil
import sys
import time

from bench import compare, generator
from bench import trace as tracing
from bench.kernels import KERNELS

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# queries sent in set-up, before the window: every program the cell's
# shapes need is compiled or loaded from the persistent cache by then
WARMUP_QUERIES = 2


def rss_mb() -> float:
    """The process's resident memory now, in MB (Linux ``statm``)."""
    with open("/proc/self/statm") as fh:
        pages = int(fh.read().split()[1])
    return pages * os.sysconf("SC_PAGE_SIZE") / 2 ** 20


def load_cell(root: str, name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic) of the cell ``name``."""
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; have {sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg["file"])) as fh:
        config = json.load(fh)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as fh:
        traffic = json.load(fh)
    return bench, cell, config, traffic


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics the cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def _compile_counter() -> list:
    import jax
    seen: list = []

    def listen(event, duration, **_kw):
        if event == COMPILE_EVENT:
            seen.append(duration)
    jax.monitoring.register_event_duration_secs_listener(listen)
    return seen


def kept_indices(seed: int, index: int, n: int, keep: int) -> list[int]:
    """The ``keep`` of query ``index``'s ``n`` records kept for the check,
    drawn from the seed."""
    if not n:
        return []
    rng = generator.stream(seed, index, 1)
    return sorted(rng.choice(n, min(keep, n), replace=False).tolist())


def _loop(entry, config, traffic, seed, first, seconds, max_queries,
          span, keep) -> dict:
    """Queries back to back from index ``first`` until ``seconds`` have
    passed (a query started inside the window is finished) or
    ``max_queries`` have run. Keeps ``keep`` records of each query,
    chosen from the seed, for the check, and the highest resident memory
    read as each query's records are assembled."""
    kept, n_rec, n_q, failed, took, rss = [], 0, 0, 0, [], 0.0
    t0 = time.perf_counter()
    while n_q < max_queries and (n_q == 0
                                 or time.perf_counter() - t0 < seconds):
        q = generator.query(config, traffic, seed, first + n_q)
        tq = time.perf_counter()
        try:
            with span("bench.query"):
                with span("bench.opgen"):
                    wls = entry.build(q)
                with span("bench.call"):
                    res = entry.call(q, wls)
                with span("bench.records"):
                    recs = entry.records(res)
        except Exception as exc:  # a failed query counts, the run goes on
            failed += 1
            print(f"query {first + n_q} failed: {exc!r}", file=sys.stderr)
            n_q += 1
            continue
        took.append(time.perf_counter() - tq)
        rss = max(rss, rss_mb())
        n_rec += len(recs)
        n_q += 1
        idx = kept_indices(seed, q["index"], len(recs), keep)
        kept.append((q, len(recs), [(i, recs[i]) for i in idx]))
        del res, recs
    return {"seconds": time.perf_counter() - t0, "queries": n_q,
            "records": n_rec, "failed": failed, "kept": kept, "took": took,
            "rss_mb": rss}


def _spread(xs: list) -> str:
    if not xs:
        return "-"
    xs = sorted(xs)
    return f"{xs[0]:.4f}/{xs[len(xs) // 2]:.4f}/{xs[-1]:.4f}"


def check(entry_mod, config: dict, kept: list, seed: int,
          sample: int) -> dict:
    """Compare a sample (drawn from the seed) of the kept records with
    the reference."""
    pool = [(qi, i) for qi, (_q, _n, recs) in enumerate(kept)
            for i, _r in recs]
    rng = generator.stream(seed, 1 << 32)
    pick = sorted(rng.choice(len(pool), min(sample, len(pool)),
                             replace=False).tolist()) if pool else []
    by_q: dict[int, list[int]] = {}
    for p in pick:
        qi, i = pool[p]
        by_q.setdefault(qi, []).append(i)
    ref, got, n_size = [], [], 0
    for qi, idx in by_q.items():
        q, n, recs = kept[qi]
        rec_of = dict(recs)
        ref += entry_mod.reference(config, q, idx)
        got += [rec_of[i] for i in idx]
        # every query returned as many records as its grid has cells
        n_size += int(n != entry_mod.Entry.size(q))
    dev = compare.deviation(ref, got, entry_mod.EXACT, entry_mod.FLOOR)
    dev["mismatches"] += n_size
    dev["compared"] = len(got)
    return dev


def run(root: str, cell_name: str, seed: int, seconds: float, traced: bool,
        t_start: float) -> dict:
    """One run of ``cell_name``; returns the result line's object."""
    import jax
    # jax and its runtime are up: what the resident memory grows by from
    # here is the simulator's (its modules, traces, programs and caches);
    # printed beside the compile count
    rss_base = rss_mb()
    bench, cell, config, traffic = load_cell(root, cell_name)
    entry_mod = importlib.import_module(f"bench.entries.{traffic['entry']}")
    compiles = _compile_counter()
    # host annotations; with the profiler on they land in its trace on
    # the device's clock
    span = jax.profiler.TraceAnnotation
    entry = entry_mod.Entry(config, traffic)
    t_entry = time.perf_counter() - t_start
    warm = _loop(entry, config, traffic, seed, 0, float("inf"),
                 WARMUP_QUERIES, span, 0)
    setup_s = time.perf_counter() - t_start
    first = warm["queries"]
    n_compiles = len(compiles)
    devices = jax.devices()[:cell["chips"]]
    check_cfg = traffic["check"]

    if traced:
        out_dir = os.path.join(root, "results", "bench", cell_name)
        shutil.rmtree(out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out_dir, profiler_options=opts)
        with span(tracing.WINDOW):
            win = _loop(entry, config, traffic, seed, first, seconds,
                        traffic["trace_queries"], span,
                        check_cfg["kept_per_query"])
        jax.profiler.stop_trace()
    else:
        win = _loop(entry, config, traffic, seed, first, seconds,
                    1 << 62, span, check_cfg["kept_per_query"])
    in_window = len(compiles) - n_compiles
    stats = [d.memory_stats() or {} for d in devices]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    print(f"compiles: {n_compiles} in set-up, {in_window} inside the "
          f"window; queries {win['queries']}, records {win['records']}, "
          f"window {win['seconds']!r} s; query s min/median/max "
          f"{_spread(win['took'])}; set-up: entry ready at {t_entry:.2f} s, "
          f"warm-up queries {[round(t, 2) for t in warm['took']]} s; "
          f"resident MB {rss_base:.1f} at start, {win['rss_mb']:.1f} at "
          f"most in the window; host load {os.getloadavg()[0]:.2f} on "
          f"{os.cpu_count()} cores",
          flush=True)
    dev_info = {"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices),
                "memory_peak_bytes": int(mem_peak)}
    metrics, breakdown = {}, None
    if traced:
        files = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        red = tracing.reduce(tracing.load(files[-1]), KERNELS)
        dev_info["busy_s"] = red["busy_ns"] / 1e9
        dev_info["window_s"] = red["window_ns"] / 1e9
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        for m in cell_metrics(bench, cell_name, "per_layer"):
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            v = reader.read(red)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"records_per_s": win["records"] / win["seconds"],
               "setup_s": setup_s}
        for m in cell_metrics(bench, cell_name, "end_to_end"):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    dev = check(entry_mod, config, win["kept"], seed,
                check_cfg["sample"])
    limits = {"max_rel_dev": entry_mod.LIMIT, "mismatches": 0}
    correct = (win["failed"] == 0 and dev["compared"] > 0
               and dev["max_rel_dev"] <= limits["max_rel_dev"]
               and dev["mismatches"] <= limits["mismatches"])
    compared = {k: {"value": dev[k], "limit": limits[k]} for k in limits}
    if dev["first"]:
        print(f"check first mismatch: {dev['first']}", file=sys.stderr)
    print(f"check records compared: {dev['compared']}", file=sys.stderr)
    for k, v in compared.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    out = {"correct": bool(correct), "attempted": win["queries"],
           "failed": win["failed"], "metrics": metrics, "device": dev_info}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = compared
    return out
