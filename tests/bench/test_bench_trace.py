"""The reduction from a trace to the per-layer metrics, on a small
trace whose numbers are known (``trace_small.json``: a 1000 ns window,
two queries, device busy 350 ns)."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402
from bench.kernels import KERNELS  # noqa: E402


@pytest.fixture
def red():
    with open(os.path.join(os.path.dirname(__file__),
                           "trace_small.json")) as fh:
        return trace.reduce(json.load(fh), KERNELS)


def test_busy_union_window_and_kernels(red):
    assert red["window_ns"] == 1000 and red["busy_ns"] == 350
    assert red["queries"] == 2 and red["n_devices"] == 1
    assert red["kernel_ns"] == {"sweep_kernel": 200, "scan_kernel": 100}


def test_breakdown_ops_and_labelled_gaps(red):
    assert red["device_ops"] == [["a", 2e-7], ["b", 1.5e-7], ["c", 5e-8]]
    assert red["idle_gaps"] == [["bench.call", 3.5e-7],
                                ["bench.records", 2e-7],
                                ["bench.call", 1e-7]]


@pytest.mark.parametrize("name,want", [
    ("opgen_ms", 3e-5), ("records_ms", 5e-5), ("call_host_ms", 2.55e-4),
    ("sweep_kernel_ms", 1e-4), ("scan_kernel_ms", 5e-5),
    ("idle_share", 65.0)])
def test_metric_readers(red, name, want):
    import importlib
    got = importlib.import_module(f"bench.metrics.{name}").read(red)
    assert got == pytest.approx(want, rel=1e-12)


def test_readers_return_nothing_where_nothing_ran(red):
    import importlib
    empty = dict(red, kernel_ns={}, spans=[], queries=0)
    for name in ("opgen_ms", "records_ms", "call_host_ms",
                 "sweep_kernel_ms", "scan_kernel_ms"):
        assert importlib.import_module(
            f"bench.metrics.{name}").read(empty) is None


def test_union_clips_and_merges():
    assert trace.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == \
        [[1, 4], [5, 10]]


def test_load_reads_annotations_from_a_recorded_trace(tmp_path):
    import glob
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        with jax.profiler.TraceAnnotation("bench.query"):
            jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    tr = trace.load(path[0])
    names = [s[0] for s in tr["spans"]]
    assert names == [trace.WINDOW, "bench.query"]
    assert tr["spans"][0][1] <= tr["spans"][1][1] <= tr["spans"][1][2] \
        <= tr["spans"][0][2]
    assert trace.reduce(tr, KERNELS)["queries"] == 1
