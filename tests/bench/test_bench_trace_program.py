"""The readers of the program's own spans (``regate.*``), on a small
trace whose numbers are known (``trace_program.json``: a 1000 ns
window, a sweep query and a plane query, device busy 230 ns). It holds
the trace twice, as each loader keeps it: ``bench`` as ``trace.load``
does (the benchmark's annotations and the device's lines) and
``program`` as ``program_spans.load`` does (the ``bench.query`` spans,
the program's spans and their stats).

``program_spans.of`` finds the program's part by searching the
checkout's recorded traces for the one whose ``bench.query`` spans are
the reduction's; the tests give it the fixture's files in a temporary
checkout."""
import copy
import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import program_spans, trace  # noqa: E402
from bench.kernels import KERNELS  # noqa: E402

HERE = os.path.dirname(__file__)
PROGRAM_READERS = ("put_ms", "harvest_ms", "host_columns_ms",
                   "exec_rows_ms", "folds_ms", "puts_per_query",
                   "pulls_per_query", "scan_fill_pct", "dispatch_ms",
                   "assemble_ms", "policy_host_ms", "plane_host_ms")


def _fixture() -> dict:
    with open(os.path.join(HERE, "trace_program.json")) as fh:
        return json.load(fh)


def _checkout(root, monkeypatch, programs: dict) -> None:
    """Recorded traces under ``root/results/bench``, one file per entry
    of ``programs`` (name to ``program_spans.load``'s form), in that
    order of age; ``root`` stands in for the checkout."""
    by_path = {}
    for age, (name, prog) in enumerate(programs.items()):
        path = root / "results" / "bench" / name / "host.xplane.pb"
        path.parent.mkdir(parents=True)
        path.write_bytes(b"")
        os.utime(path, ns=(10 ** 18 + age, 10 ** 18 + age))
        by_path[str(path)] = prog
    monkeypatch.setattr(program_spans, "ROOT", str(root))
    monkeypatch.setattr(program_spans, "load",
                        lambda path: copy.deepcopy(by_path[path]))
    program_spans._load.cache_clear()


@pytest.fixture
def red(tmp_path, monkeypatch):
    fx = _fixture()
    _checkout(tmp_path, monkeypatch, {"cell": fx["program"]})
    return trace.reduce(fx["bench"], KERNELS)


def _read(name, red):
    return importlib.import_module(f"bench.metrics.{name}").read(red)


@pytest.mark.parametrize("name,want", [
    ("put_ms", 3e-5), ("harvest_ms", 1.35e-4), ("host_columns_ms", 1e-5),
    ("exec_rows_ms", 1.5e-5), ("folds_ms", 4e-5),
    ("puts_per_query", 34.5), ("pulls_per_query", 58.0),
    ("scan_fill_pct", 75.0),
    # kernel spans 140 + 50 + 80 ns with 120 + 30 + 60 ns device busy
    ("dispatch_ms", 3e-5),
    ("assemble_ms", 5e-5),
    # 380 + 260 ns of evaluate_batch with 140 + 60 ns device busy
    ("policy_host_ms", 2.2e-4),
    # 460 ns of program_plane_batch, 90 ns busy, 200 ns policy side
    ("plane_host_ms", 8.5e-5)])
def test_program_span_readers(red, name, want):
    assert _read(name, red) == pytest.approx(want, rel=1e-12)


def test_the_run_is_found_by_its_queries(tmp_path, monkeypatch):
    fx = _fixture()
    other = copy.deepcopy(fx["program"])
    other["queries"][0][1] += 1
    for sp in other["spans"]:
        if sp[0] == "regate.harvest":
            sp[2] += 1000
    # the newest trace is another run's: its queries differ
    _checkout(tmp_path, monkeypatch, {"a": fx["program"], "b": other})
    red = trace.reduce(fx["bench"], KERNELS)
    assert program_spans.of(red)["spans"] == fx["program"]["spans"]
    assert _read("harvest_ms", red) == pytest.approx(1.35e-4, rel=1e-12)


@pytest.mark.parametrize("name", PROGRAM_READERS)
def test_program_readers_return_nothing_without_the_hook(tmp_path,
                                                         monkeypatch, name):
    # a traced run of a program without the hook: its trace holds the
    # benchmark's queries and no regate.* span or count
    fx = _fixture()
    _checkout(tmp_path, monkeypatch, {"cell": dict(
        fx["program"], spans=[], counts=[])})
    assert _read(name, trace.reduce(fx["bench"], KERNELS)) is None


@pytest.mark.parametrize("name", PROGRAM_READERS)
def test_program_readers_return_nothing_without_a_trace(tmp_path,
                                                        monkeypatch, name):
    fx = _fixture()
    _checkout(tmp_path, monkeypatch, {})
    assert _read(name, trace.reduce(fx["bench"], KERNELS)) is None
