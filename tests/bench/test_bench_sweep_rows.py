"""The readers of the sweep kernel's work counts (``sweep_rows_per_query``,
``sweep_ns_per_row``): on the small recorded trace of
``trace_program.json`` with the counts added to its sweep harvests, on
the same trace without them, and on a real profiler trace of a tiny
``evaluate_batch`` on the CPU, whose counts are the stack's own."""
import copy
import dataclasses
import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402
from bench.kernels import KERNELS  # noqa: E402
from test_bench_program_spans import GRID, NPUS, _profiled  # noqa: E402
from test_bench_trace_program import _checkout, _fixture  # noqa: E402

READERS = ("sweep_rows_per_query", "sweep_ns_per_row")
# the fixture's two sweep-kernel calls, harvested at 250 and 800 ns; the
# harvest at 600 ns is the event scan's and counts no rows
COUNTS = {250: {"ops": 1000, "mm_ops": 600, "knobs": 240},
          800: {"ops": 200, "mm_ops": 90, "knobs": 8}}


def _read(name, red):
    return importlib.import_module(f"bench.metrics.{name}").read(red)


def _with_counts(program: dict) -> dict:
    out = copy.deepcopy(program)
    for name, start, stats in out["counts"]:
        if name == "regate.harvest" and start in COUNTS:
            stats.update(COUNTS[start])
    return out


def test_readers_on_a_recorded_trace(tmp_path, monkeypatch):
    fx = _fixture()
    _checkout(tmp_path, monkeypatch, {"cell": _with_counts(fx["program"])})
    red = trace.reduce(fx["bench"], KERNELS)
    # two queries; the kernel ran 120 + 60 ns of jit_kern
    assert red["queries"] == 2 and red["kernel_ns"]["sweep_kernel"] == 180
    assert _read("sweep_rows_per_query", red) == (1000 + 200) / 2
    assert _read("sweep_ns_per_row", red) == pytest.approx(
        180 / (1000 * 240 + 200 * 8), rel=1e-12)


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_without_the_counts(tmp_path, monkeypatch,
                                                   name):
    # the program before the counts: harvests carry arrays and bytes only
    fx = _fixture()
    _checkout(tmp_path, monkeypatch, {"cell": fx["program"]})
    assert _read(name, trace.reduce(fx["bench"], KERNELS)) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_without_a_trace(tmp_path, monkeypatch,
                                                name):
    fx = _fixture()
    _checkout(tmp_path, monkeypatch, {})
    assert _read(name, trace.reduce(fx["bench"], KERNELS)) is None


def test_sweep_harvest_counts_the_stack(tmp_path, monkeypatch):
    from repro.core.opgen import paper_suite, stack_traces
    from repro.core.policies import evaluate_batch
    wls = [dataclasses.replace(w) for w in paper_suite()[:3]]
    st = stack_traces(wls)
    _res, red, prog, _made = _profiled(
        tmp_path, monkeypatch,
        lambda: evaluate_batch(wls, NPUS, ("NoPG", "ReGate-Full"), GRID,
                               backend="jax"))
    stats = [s for n, _t, s in prog["counts"] if n == "regate.harvest"]
    assert len(stats) == len(NPUS)
    for s in stats:
        assert (s["ops"], s["mm_ops"], s["knobs"]) == (
            st.n_ops, int(st.has_mm.sum()), len(GRID))
        assert s["arrays"] == 1 and s["bytes"] > 0
    assert _read("sweep_rows_per_query", red) == len(NPUS) * st.n_ops
