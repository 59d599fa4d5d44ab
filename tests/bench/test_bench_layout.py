"""The harness is driven by files: a configuration, a traffic mix and a
per-layer metric placed in their directories are found by name. The
same runs, at a tiny size on the CPU, show that ``correct`` comes out
false when the timed path is broken underneath."""
import json
import os
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

TINY_DENSE = {
    "name": "tiny-dense",
    "arch": {"name": "tiny-dense", "family": "dense", "n_layers": 2,
             "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
             "d_ff": 128, "vocab_size": 256, "qkv_bias": True,
             "tie_embeddings": False, "act": "silu"},
    "shapes": {
        "train_tiny": {"kind": "train", "seq_len": 64, "global_batch": 8,
                       "n_chips": 4, "tp": 2},
        "decode_tiny": {"kind": "decode", "seq_len": 64, "global_batch": 8,
                        "n_chips": 4, "tp": 2}},
}
TINY_SSM = {
    "name": "tiny-ssm",
    "arch": {"name": "tiny-ssm", "family": "ssm", "n_layers": 2,
             "d_model": 64, "n_heads": 0, "n_kv_heads": 0, "head_dim": 0,
             "d_ff": 0, "vocab_size": 256, "tie_embeddings": True,
             "act": "silu",
             "ssm": {"d_state": 16, "head_dim": 16, "expand": 2,
                     "conv_width": 4, "n_groups": 1, "chunk": 8}},
    "shapes": {
        "prefill_tiny": {"kind": "prefill", "seq_len": 64,
                         "global_batch": 2, "n_chips": 1, "tp": 1},
        "decode_tiny": {"kind": "decode", "seq_len": 64,
                        "global_batch": 2, "n_chips": 1, "tp": 1}},
}
TINY_SWEEP = {
    "entry": "sweep_grid", "batch_fractions": [0.5, 1.0],
    "npus": ["NPU-B", "NPU-D"], "policies": ["NoPG", "ReGate-Full"],
    "knobs": {"delay_scale": {"n": 2, "lo": 0.5, "hi": 4.0, "scale": "log"},
              "leak_off_logic": {"n": 2, "lo": 0.01, "hi": 0.4}},
    "check": {"kept_per_query": 4, "sample": 16}, "trace_queries": 3,
}
TINY_PLANE = {
    "entry": "program_plane", "npus": ["NPU-A", "NPU-E"],
    "knobs": {"delay_scale": {"values": [1.0, 4.0]},
              "window_scale": {"n": 2, "lo": 0.25, "hi": 2.0}},
    "check": {"kept_per_query": 4, "sample": 16}, "trace_queries": 3,
}


def _bench_dir(tmp_path, per_layer=()):
    """A checkout holding one tiny sweep cell and one tiny plane cell,
    added as files only."""
    for sub in ("configs", "traffic"):
        (tmp_path / "bench" / sub).mkdir(parents=True)
    for name, data in (("configs/tiny-dense", TINY_DENSE),
                       ("configs/tiny-ssm", TINY_SSM),
                       ("traffic/tiny_sweep", TINY_SWEEP),
                       ("traffic/tiny_plane", TINY_PLANE)):
        (tmp_path / "bench" / f"{name}.json").write_text(json.dumps(data))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"] = [
        {"name": c["name"], "source": "test", "file":
         f"bench/configs/{c['name']}.json", "reduced": [], "why": "test"}
        for c in (TINY_DENSE, TINY_SSM)]
    bench["workloads"] = [
        {"name": "tiny-dense.tiny_sweep", "config": "tiny-dense",
         "traffic": "tiny_sweep", "chips": 1, "why": "test"},
        {"name": "tiny-ssm.tiny_plane", "config": "tiny-ssm",
         "traffic": "tiny_plane", "chips": 1, "why": "test"}]
    bench["per_layer"] = list(per_layer)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def _run(root, cell, traced=False, seed=2 ** 31 + 3):
    return harness.run(root, cell, seed, 0.3, traced, time.perf_counter())


@pytest.mark.parametrize("cell", ["tiny-dense.tiny_sweep",
                                  "tiny-ssm.tiny_plane"])
def test_added_config_and_mix_run_by_name(tmp_path, cell):
    out = _run(_bench_dir(tmp_path), cell)
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"records_per_s", "setup_s"}
    assert list(out)[-1] == "check"
    assert out["check"]["mismatches"] == {"value": 0, "limit": 0}


def test_added_metric_reader_is_found_by_name(tmp_path, monkeypatch):
    import bench.metrics
    extra = tmp_path / "readers"
    extra.mkdir()
    (extra / "queries_traced.py").write_text(
        "def read(red):\n    return float(red['queries'])\n")
    monkeypatch.setattr(bench.metrics, "__path__",
                        list(bench.metrics.__path__) + [str(extra)])
    root = _bench_dir(tmp_path / "co", per_layer=[
        {"name": "queries_traced", "unit": "queries", "better": "higher",
         "source": "program_span", "layer": "harness",
         "moves": "records_per_s"},
        {"name": "records_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "record assembly",
         "moves": "records_per_s"}])
    out = _run(root, "tiny-dense.tiny_sweep", traced=True)
    assert out["correct"], out["check"]
    assert out["metrics"]["queries_traced"]["value"] == 3.0
    assert out["metrics"]["records_ms"]["value"] > 0
    assert "breakdown" in out and out["device"]["window_s"] > 0


def _altered_records(cls, field, bump):
    orig = cls.records

    def records(self):
        return [dict(r, **{field: bump(r[field])}) for r in orig(self)]
    return records


def test_altered_answer_is_not_correct(tmp_path, monkeypatch):
    from repro.core.policies import BatchResult
    monkeypatch.setattr(BatchResult, "records", _altered_records(
        BatchResult, "total_j", lambda v: v * (1 + 1e-7)))
    out = _run(_bench_dir(tmp_path), "tiny-dense.tiny_sweep")
    assert not out["correct"]
    assert out["check"]["max_rel_dev"]["value"] > 1e-8


def _half_sweep(monkeypatch):
    import importlib
    from repro.core.policies import KnobGrid
    sweep_mod = importlib.import_module("repro.core.sweep")
    orig = sweep_mod.sweep_grid

    def half(wls, npus, policies, grid, **kw):
        return orig(wls, npus, policies, grid=KnobGrid(
            **{**{k: getattr(grid, k) for k in KnobGrid.COLUMNS},
               "delay_scale": grid.delay_scale[:1]}), **kw)
    monkeypatch.setattr(sweep_mod, "sweep_grid", half)


def _half_plane(monkeypatch):
    import importlib
    plane_mod = importlib.import_module("repro.core.program_plane")
    orig = plane_mod.program_plane_batch

    def half(wls, npus, knobs, **kw):
        return orig(wls, npus, knobs[:len(knobs) // 2], **kw)
    monkeypatch.setattr(plane_mod, "program_plane_batch", half)


@pytest.mark.parametrize("cell,halve", [
    ("tiny-dense.tiny_sweep", _half_sweep),
    ("tiny-ssm.tiny_plane", _half_plane)])
def test_half_the_batch_left_out_is_not_correct(tmp_path, monkeypatch, cell,
                                                halve):
    halve(monkeypatch)
    out = _run(_bench_dir(tmp_path), cell)
    assert not out["correct"]
    assert out["check"]["mismatches"]["value"] > 0


def test_altered_executor_count_is_not_correct(tmp_path, monkeypatch):
    from repro.core.program_plane import ProgramPlaneBatch
    monkeypatch.setattr(ProgramPlaneBatch, "records", _altered_records(
        ProgramPlaneBatch, "stall_cycles", lambda v: v + 1))
    out = _run(_bench_dir(tmp_path), "tiny-ssm.tiny_plane")
    assert not out["correct"]
    assert out["check"]["mismatches"]["value"] > 0


def test_missing_cell_is_refused(tmp_path):
    with pytest.raises(KeyError):
        harness.load_cell(_bench_dir(tmp_path), "no-such.cell")
    shutil.rmtree(tmp_path / "bench")
