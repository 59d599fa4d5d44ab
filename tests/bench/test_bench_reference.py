"""The plain reference against the program, and its control: at the
cells' own sizes, on the numpy path of the program (the chip run
compares the jax path the same way)."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import compare, generator  # noqa: E402
from bench.entries import program_plane, sweep_grid  # noqa: E402
from bench.reference import opgen  # noqa: E402


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "bench", kind, name + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", ["qwen2.5-14b", "mamba2-780m"])
def test_config_file_is_the_registered_model(name):
    from repro.configs.base import get_arch
    from repro.models.registry import count_params
    from bench.entries import common
    config = _load("configs", name)
    got = common.arch_config(config)
    import dataclasses
    assert dataclasses.replace(got, source="") == dataclasses.replace(
        get_arch(name), source="")
    assert opgen.n_params(config["arch"]) == count_params(got)


@pytest.mark.parametrize("name", ["qwen2.5-14b", "mamba2-780m"])
def test_reference_trace_is_the_program_trace(name):
    from repro.configs.base import ShapeConfig
    from repro.core.opgen import arch_workload
    from bench.entries import common
    config = _load("configs", name)
    arch = common.arch_config(config)
    for shape, sh in config["shapes"].items():
        for batch in (1, sh["global_batch"]):
            wl = arch_workload(arch, ShapeConfig(shape, sh["seq_len"], batch,
                                                 sh["kind"]),
                               n_chips=sh["n_chips"], tp=sh["tp"])
            ref = opgen.trace(config["arch"], sh["kind"], sh["seq_len"],
                              batch, sh["n_chips"], sh["tp"])
            assert len(ref) == len(wl.ops)
            for r, o in zip(ref, wl.ops):
                assert (r["name"], r["flops_sa"], r["flops_vu"],
                        r["bytes_hbm"], r["bytes_ici"], r["sram_demand"],
                        r["mm"], r["count"], r["collective"]) == (
                    o.name, o.flops_sa, o.flops_vu, o.bytes_hbm,
                    o.bytes_ici, o.sram_demand, o.matmul_dims, o.count,
                    o.collective)


def _sweep(seed, n=40):
    from repro.core.policies import KnobGrid, evaluate_batch
    config, traffic = _load("configs", "qwen2.5-14b"), _load("traffic",
                                                             "sweep")
    q = generator.query(config, traffic, seed, 3)
    wls = sweep_grid.Entry(config, traffic).build(q)
    recs = evaluate_batch(wls, tuple(q["npus"]), tuple(q["policies"]),
                          KnobGrid(**q["axes"]), backend="numpy").records()
    idx = sorted(generator.stream(seed, 9).choice(len(recs), n,
                                                  replace=False).tolist())
    return config, q, idx, [recs[i] for i in idx]


def _plane(seed, n=24):
    from repro.core.policies import KnobGrid
    from repro.core.program_plane import program_plane_batch
    config, traffic = _load("configs", "mamba2-780m"), _load("traffic",
                                                             "plane")
    q = generator.query(config, traffic, seed, 3)
    wls = program_plane.Entry(config, traffic).build(q)
    recs = program_plane_batch(wls, tuple(q["npus"]),
                               KnobGrid(**q["axes"]).product(),
                               backend="numpy").records()
    idx = sorted(generator.stream(seed, 9).choice(len(recs), n,
                                                  replace=False).tolist())
    return config, q, idx, [recs[i] for i in idx]


CASES = {"sweep": (_sweep, sweep_grid), "plane": (_plane, program_plane)}


@pytest.mark.parametrize("cell", sorted(CASES))
def test_reference_agrees_with_the_program(cell):
    make, mod = CASES[cell]
    config, q, idx, got = make(2 ** 31 + 17)
    dev = compare.deviation(mod.reference(config, q, idx), got, mod.EXACT,
                            mod.FLOOR)
    assert dev["mismatches"] == 0, dev["first"]
    assert dev["max_rel_dev"] <= mod.LIMIT / 100


@pytest.mark.parametrize("cell", sorted(CASES))
def test_float32_control_fails_the_comparison(cell):
    make, mod = CASES[cell]
    config, q, idx, _got = make(31)
    ref = mod.reference(config, q, idx)
    ctl = mod.reference(config, q, idx, np.float32)
    dev = compare.deviation(ref, ctl, mod.EXACT, mod.FLOOR)
    assert dev["max_rel_dev"] > 100 * mod.LIMIT or dev["mismatches"] > 0


def test_deviation_counts_every_kind_of_mismatch():
    a = [{"w": "x", "n": 3, "v": 1.0, "prog_cycles": 5}]
    assert compare.deviation(a, a)["mismatches"] == 0
    assert compare.deviation(a, a + a)["mismatches"] == 1
    assert compare.deviation(a, [dict(a[0], w="y")])["mismatches"] == 1
    assert compare.deviation(a, [dict(a[0], n=4)])["mismatches"] == 1
    assert compare.deviation(a, [{"w": "x", "n": 3}])["mismatches"] == 1
    d = compare.deviation(a, [dict(a[0], v=1.0 + 1e-6)])
    assert d["mismatches"] == 0 and 9e-7 < d["max_rel_dev"] < 1.1e-6
    assert compare.deviation(a, [dict(a[0], v=float("nan"))])[
        "mismatches"] == 1
    b = [dict(a[0], prog_x=1.0)]
    assert compare.deviation(b, [dict(b[0], prog_x=1.0 + 1e-15)],
                             exact=("prog_",))["mismatches"] == 1
