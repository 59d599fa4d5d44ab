"""The program's spans on a real profiler trace, on the CPU: a tiny
``evaluate_batch`` and a tiny ``program_plane_batch`` on the jax backend
are profiled inside the benchmark's ``bench.window`` / ``bench.query``
annotations, and the trace is read back as the harness reads it.

The spans nest as the call does, and the ``arrays``/``bytes`` counts
of the transfer spans equal the transfers the backend actually made
(counted by wrapping ``JaxBackend.asarray`` and ``to_numpy``). On the
numpy backend the hook is a no-op. A traced harness run of the tiny
cells reads every one of the program's metrics."""
import dataclasses
import glob
import importlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import program_spans, trace  # noqa: E402
from bench.kernels import KERNELS  # noqa: E402
from repro.core.backend import JaxBackend, get_backend  # noqa: E402
from repro.core.opgen import paper_suite  # noqa: E402
from repro.core.policies import PolicyKnobs, evaluate_batch  # noqa: E402
from repro.core.program_plane import program_plane_batch  # noqa: E402

GRID = (PolicyKnobs(), PolicyKnobs(delay_scale=2.0),
        PolicyKnobs(leak_off_logic=0.2))
NPUS = ("NPU-B", "NPU-D")


def _fresh(n: int) -> list:
    """New workload objects, so no stack of them is on the device yet."""
    return [dataclasses.replace(w) for w in paper_suite()[:n]]


def _profiled(tmp_path, monkeypatch, fn):
    """Run ``fn`` as one traced benchmark query; returns its result, the
    reduction (readers find the trace under ``tmp_path``), the program's
    spans and the transfers the jax backend made."""
    import jax
    made = {"puts": 0, "put_bytes": 0, "pulls": 0, "pull_bytes": 0}
    put, pull = JaxBackend.asarray, JaxBackend.to_numpy

    def asarray(self, x):
        made["puts"] += 1
        made["put_bytes"] += np.asarray(x).nbytes
        return put(self, x)

    def to_numpy(self, x):
        out = pull(self, x)
        made["pulls"] += 1
        made["pull_bytes"] += out.nbytes
        return out

    monkeypatch.setattr(JaxBackend, "asarray", asarray)
    monkeypatch.setattr(JaxBackend, "to_numpy", to_numpy)
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    out_dir = tmp_path / "results" / "bench" / "cell"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            with jax.profiler.TraceAnnotation("bench.query"):
                with jax.profiler.TraceAnnotation("bench.call"):
                    res = fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(out_dir / "**" / "*.xplane.pb"), recursive=True)[0]
    red = trace.reduce(trace.load(path), KERNELS)
    return res, red, program_spans.of(red), made


def _named(prog, name):
    return [s for s in prog["spans"] if s[0] == name]


def _inside(child, parents) -> bool:
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def _read(name, red):
    return importlib.import_module(f"bench.metrics.{name}").read(red)


def test_evaluate_batch_spans_nest_and_count_the_transfers(tmp_path,
                                                           monkeypatch):
    wls = _fresh(2)
    _res, red, prog, made = _profiled(
        tmp_path, monkeypatch,
        lambda: evaluate_batch(wls, NPUS, ("NoPG", "ReGate-Full"), GRID,
                               backend="jax"))
    top = _named(prog, "regate.evaluate_batch")
    assert len(top) == 1
    for name, n in (("regate.host_columns", 2), ("regate.put", 4),
                    ("regate.sweep_kernel", 2), ("regate.harvest", 2),
                    ("regate.assemble", 2)):
        spans = _named(prog, name)
        assert len(spans) == n, name
        assert all(_inside(s, top) for s in spans), name
    assert {n for n, _s, _st in prog["counts"]} == {
        "regate.put", "regate.harvest"}
    puts = program_spans.stats_of(red, "regate.put")
    pulls = program_spans.stats_of(red, "regate.harvest")
    assert sum(st["arrays"] for st in puts) == made["puts"] > 0
    assert sum(st["bytes"] for st in puts) == made["put_bytes"]
    assert sum(st["arrays"] for st in pulls) == made["pulls"] > 0
    assert sum(st["bytes"] for st in pulls) == made["pull_bytes"]
    assert _read("puts_per_query", red) == made["puts"]
    assert _read("pulls_per_query", red) == made["pulls"]
    for name in ("put_ms", "harvest_ms", "host_columns_ms", "dispatch_ms",
                 "assemble_ms", "policy_host_ms"):
        assert _read(name, red) > 0, name
    for name in ("exec_rows_ms", "folds_ms", "scan_fill_pct",
                 "plane_host_ms"):
        assert _read(name, red) is None, name


def test_program_plane_spans_nest_and_count_the_transfers(tmp_path,
                                                          monkeypatch):
    wls = _fresh(2)
    res, red, prog, made = _profiled(
        tmp_path, monkeypatch,
        lambda: program_plane_batch(wls, NPUS, GRID, backend="jax"))
    top = _named(prog, "regate.program_plane_batch")
    assert len(top) == 1
    for name in ("regate.exec_rows", "regate.scan_kernel", "regate.folds",
                 "regate.evaluate_batch"):
        spans = _named(prog, name)
        assert len(spans) == 1 and _inside(spans[0], top), name
    policy = _named(prog, "regate.evaluate_batch")
    for name in ("regate.sweep_kernel", "regate.assemble"):
        assert all(_inside(s, policy) for s in _named(prog, name)), name
    assert all(_inside(s, top) for s in _named(prog, "regate.put")
               + _named(prog, "regate.harvest"))
    rows = len(wls) * len(NPUS) * len(res.triples)
    (scan,) = program_spans.stats_of(red, "regate.scan_kernel")
    assert scan["rows"] == rows
    assert scan["events"] == int(res.n_events.sum())
    assert scan["e_max"] == int(res.n_events.max())
    assert _read("scan_fill_pct", red) == pytest.approx(
        100.0 * scan["events"] / (rows * scan["e_max"]), rel=1e-12)
    assert _read("puts_per_query", red) == made["puts"] > 0
    assert _read("pulls_per_query", red) == made["pulls"] > 0
    for name in ("put_ms", "harvest_ms", "exec_rows_ms", "folds_ms",
                 "dispatch_ms", "assemble_ms", "policy_host_ms",
                 "plane_host_ms"):
        assert _read(name, red) > 0, name
    assert {n for n, _s, _st in prog["counts"]} == {
        "regate.put", "regate.scan_kernel", "regate.harvest"}


def _never() -> dict:
    raise AssertionError("counted with no profiler recording")


def test_counts_wait_for_a_recording_profiler():
    import jax
    with get_backend("jax").span("regate.put", _never):
        jnp_sum = jax.numpy.ones(3).sum()
    assert float(jnp_sum) == 3.0


def test_the_benchmark_loader_keeps_no_program_span(tmp_path, monkeypatch):
    # the reduction the existing readers take is the one they took
    # before the hook: trace.load keeps only the bench.* annotations
    wls = _fresh(2)
    _res, red, prog, _made = _profiled(
        tmp_path, monkeypatch,
        lambda: program_plane_batch(wls, NPUS, GRID, backend="jax"))
    assert len(prog["spans"]) > 10
    assert {s[0] for s in red["spans"]} == {"bench.query", "bench.call"}


def test_numpy_backend_span_is_a_no_op(tmp_path, monkeypatch):
    bk = get_backend("numpy")
    with bk.span("regate.put", _never) as inside:
        assert inside is None
    wls = _fresh(2)
    plain = program_plane_batch(wls, NPUS, GRID, backend="numpy").records()
    res, _red, prog, made = _profiled(
        tmp_path, monkeypatch,
        lambda: program_plane_batch(wls, NPUS, GRID, backend="numpy"))
    assert prog["spans"] == [] and prog["counts"] == []
    assert made == {"puts": 0, "put_bytes": 0, "pulls": 0, "pull_bytes": 0}
    assert res.records() == plain


@pytest.mark.parametrize("cell,names", [
    ("tiny-dense.tiny_sweep",
     ("put_ms", "harvest_ms", "host_columns_ms", "puts_per_query",
      "pulls_per_query", "dispatch_ms", "assemble_ms", "policy_host_ms")),
    ("tiny-ssm.tiny_plane",
     ("put_ms", "harvest_ms", "exec_rows_ms", "folds_ms", "puts_per_query",
      "pulls_per_query", "scan_fill_pct", "dispatch_ms", "assemble_ms",
      "policy_host_ms", "plane_host_ms"))])
def test_traced_harness_run_reads_the_program_metrics(tmp_path, monkeypatch,
                                                      cell, names):
    import json
    from test_bench_layout import _bench_dir, _run
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        per_layer = [dict(m, workloads=[cell])
                     for m in json.load(fh)["per_layer"]
                     if m["name"] in names]
    root = _bench_dir(tmp_path, per_layer=per_layer)
    # the harness writes its traces under the root it is given, and the
    # readers search the checkout's: here the two are the same
    monkeypatch.setattr(program_spans, "ROOT", root)
    out = _run(root, cell, traced=True)
    assert out["correct"], out["check"]
    assert sorted(out["metrics"]) == sorted(names)
    assert all(out["metrics"][n]["value"] > 0 for n in names)
