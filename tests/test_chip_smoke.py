"""CPU rehearsal of ``chip_smoke.py``: every phase at a tiny size, the
four-device mesh phase on virtual CPU devices (subprocess: the device
count is fixed before jax initializes), the refusal to run anywhere but
on a TPU, and the comparison that decides each phase."""
import math
import os
import shutil
import subprocess
import sys
import textwrap
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402
from repro.core.opgen import paper_suite  # noqa: E402
from repro.core.policies import POLICIES, KnobGrid  # noqa: E402

TINY_GRID = KnobGrid(delay_scale=(1.0, 2.0), leak_off_logic=(0.03, 0.2))


def test_sweep_phase_tiny():
    wls = paper_suite()[:2] + cs.sweep_workloads()[-1:]
    r = cs.sweep_phase(wls, ("NPU-B", "NPU-D"), TINY_GRID, subsample=2)
    assert r["cells"] == 3 * 2 * len(POLICIES) * 4
    assert r["checked_cells"] == r["cells"] // 2
    assert r["max_rel_dev"] <= cs.RTOL
    assert r["wall_s"] > 0 and r["cells_per_s"] > 0


def test_program_plane_phase_tiny():
    r = cs.program_plane_phase(paper_suite()[:2], ("NPU-B",),
                               KnobGrid(delay_scale=(1.0, 4.0),
                                        leak_off_logic=(None, 0.1)))
    assert r["cells"] == 2 * 1 * 4
    assert r["max_rel_dev"] <= cs.RTOL


def test_fleet_phase_tiny():
    sc = cs.fleet_scenario(n_epochs=2)
    assert sc.n_chips == 4096 and sc.n_epochs == 2
    r = cs.fleet_phase(sc, KnobGrid(delay_scale=(1.0, 2.0)))
    assert r["epochs"] == 2 and r["requests"] > 0
    assert r["max_rel_dev"] <= cs.RTOL


_MESH_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.getcwd())
    import chip_smoke as cs
    from repro.core.opgen import paper_suite
    from repro.core.policies import KnobGrid

    res = cs.mesh_phase(
        paper_suite()[:3], ("NPU-B", "NPU-D"),
        KnobGrid(delay_scale=(0.5, 1.0, 2.0), leak_off_logic=(0.03, 0.2),
                 sa_width=(None, 256)).product(),
        paper_suite()[:2], ("NPU-B",),
        KnobGrid(delay_scale=(1.0, 4.0), window_scale=(1.0, 0.5)).product(),
        n_dev=4)
    assert len(res) == 5, res
    for name, r in res.items():
        print(cs._line("cpu", name, r))
    print("MESH_PHASE_OK")
""")


def test_mesh_phase_on_4_virtual_devices():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], cwd=ROOT,
                       capture_output=True, text=True, timeout=600, env=env)
    assert "MESH_PHASE_OK" in r.stdout, r.stdout + r.stderr
    assert r.stdout.count("max_rel_dev=") == 5


def test_refuses_a_cpu(capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert "'cpu'" in out.err
    assert out.out == ""


def test_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode != 0
    assert r.stdout == ""


REC = {"workload": "w", "knob_idx": 3, "sa_width": None, "runtime_s": 2.0,
       "prog_cycles": 10.0, "total_j": 1e-12}


@pytest.mark.parametrize("field,value", [
    ("workload", "x"),          # label
    ("knob_idx", 4),            # integer
    ("sa_width", 256),          # None vs value
    ("prog_cycles", 10.000001),  # executor field: exact
    ("runtime_s", math.nan),    # non-finite
])
def test_comparison_rejects(field, value):
    with pytest.raises(cs.Mismatch):
        cs.max_rel_dev([REC], [dict(REC, **{field: value})],
                       exact=cs.EXACT_FIELDS)


def test_comparison_measures_and_limits():
    got = dict(REC, runtime_s=2.0 * (1 + 2e-10), total_j=1e-12 + 1e-20)
    dev = cs.max_rel_dev([REC], [got], exact=cs.EXACT_FIELDS)
    assert dev == pytest.approx(1e-8, rel=1e-3)
    with pytest.raises(cs.Mismatch):
        cs._check(dev, "total_j")
    # a floor of 1 measures sub-unit values absolutely
    dev1 = cs.max_rel_dev([REC], [got], floor=1.0)
    assert dev1 == pytest.approx(2e-10, rel=1e-3)
    assert cs._check(dev1, "runtime") == dev1
    with pytest.raises(cs.Mismatch):
        cs.max_rel_dev([REC], [REC, REC])
    with pytest.raises(cs.Mismatch):
        cs.max_rel_dev([REC], [{k: REC[k] for k in list(REC)[:-1]}])


def test_span_check_rejects_one_device():
    mesh = types.SimpleNamespace(
        devices=np.array(["d0", "d1", "d2", "d3"], dtype=object))
    cs._require_span([frozenset({"d0", "d1", "d2", "d3"})], mesh, "ok")
    with pytest.raises(cs.Mismatch):
        cs._require_span([frozenset({"d0"})], mesh, "chip 0 only")
