"""Bring-up smoke run of the simulator's jax path on a TPU.

Drives the jax path once, in this one process, through the public entry
points with ``backend="jax"``, and checks every phase against the same
kernels' numpy instantiation in the same run:

* sweep          ``sweep_grid``: ``paper_suite()`` plus qwen2.5-14b at
                 ``decode_32k`` x 5 NPUs x 5 policies x the 240-knob fine
                 grid; records <= 1e-9 on a knob-subsampled grid, same
                 order;
* program plane  ``sweep_program_plane``: ``paper_suite()`` x (NPU-B,
                 NPU-D) x 8 knobs; executor integers exact, the rest of
                 each record <= 1e-9;
* fleet          ``sweep_fleet``: the 4096-chip ``benchmarks/perf_fleet``
                 scenario cut to a few epochs, unguarded; summary
                 <= 1e-9 and no guard events.

That checks that the jax backend computes what the backend-neutral
kernels compute on the host. The independent check on the chip, against
a plain reference that imports nothing of the program, is
``bench/run.py``'s.

With ``--chips 4`` it runs only the ``jax_mesh=`` paths instead (GSPMD
``sweep_mesh(wl=4)``, shard_map ``sweep_mesh(wl=2, knob=2)`` and the
program plane on a ``("wl",)`` mesh of 4), each against the
single-device jax run and the numpy instantiation.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # the mesh paths on a 4-chip host

Refuses to run (non-zero exit) unless jax's first device is a TPU. The
last line of stdout is ``{"ok": true, "device": {...}}``. Each phase's
function takes its sizes, so the tests rehearse them at a tiny size on
the CPU.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from benchmarks.perf_fleet import GRID as FLEET_GRID  # noqa: E402
from benchmarks.perf_fleet import build_scenario  # noqa: E402
from benchmarks.perf_program_plane import GRID as PLANE_GRID  # noqa: E402
from repro.configs.base import SHAPES, get_arch  # noqa: E402
from repro.core.backend import get_backend  # noqa: E402
from repro.core.hw import NPUS  # noqa: E402
from repro.core.opgen import arch_workload, paper_suite  # noqa: E402
from repro.core.policies import (POLICIES, KnobGrid,  # noqa: E402
                                 evaluate_batch)
from repro.core.program_plane import program_plane_batch  # noqa: E402
from repro.core.sweep import (sweep_fleet, sweep_grid,  # noqa: E402
                              sweep_program_plane)
from repro.parallel import jax_compat  # noqa: E402

RTOL = 1e-9
# the 240-point fine knob grid of the sweep phase (§6.5 axes)
SWEEP_AXES = dict(
    delay_scale=(0.25, 0.5, 1.0, 2.0, 4.0, 8.0),
    leak_off_logic=(0.01, 0.03, 0.1, 0.2, 0.4),
    leak_sram_sleep=(0.1, 0.25, 0.4, 0.6),
    leak_sram_off=(0.002, 0.02),
)
SWEEP_SUBSAMPLE = 16  # every 16th knob of the 240-point grid is checked
FLEET_EPOCHS = 8
PLANE_NPUS = ("NPU-B", "NPU-D")
# executor-side program-plane fields: integers, compared exactly
EXACT_FIELDS = ("prog_", "n_events", "stall_", "wakes_prog", "setpm_prog")
PLANE_CMP = {"exact": EXACT_FIELDS, "floor": 1.0}


class Mismatch(AssertionError):
    """The jax path disagrees with the kernels' numpy instantiation."""


def max_rel_dev(ref: list[dict], got: list[dict],
                exact: tuple[str, ...] = (), floor: float = 1e-30) -> float:
    """Largest deviation of ``got`` from ``ref`` over every float field,
    relative to the larger magnitude or ``floor``, whichever is bigger
    (the sweep and fleet tests use 1e-30; the program-plane tests use
    1.0, because their ``gated_frac_absdiff_*`` fields are differences
    of near-equal fractions). Raises ``Mismatch`` if the record counts,
    order, field sets, labels, integers, fields named by an ``exact``
    prefix, or non-finite values differ."""
    if len(ref) != len(got):
        raise Mismatch(f"{len(got)} records, reference has {len(ref)}")
    worst = 0.0
    for i, (a, b) in enumerate(zip(ref, got)):
        if a.keys() != b.keys():
            raise Mismatch(f"record {i}: fields differ: "
                           f"{sorted(a.keys() ^ b.keys())}")
        for k, va in a.items():
            vb = b[k]
            if not isinstance(va, float) or k.startswith(exact):
                if va != vb:
                    raise Mismatch(f"record {i} {k}: {vb!r} != {va!r}")
            elif not (math.isfinite(va) and math.isfinite(vb)):
                if not (va == vb or (math.isnan(va) and math.isnan(vb))):
                    raise Mismatch(f"record {i} {k}: {vb!r} != {va!r}")
            else:
                worst = max(worst,
                            abs(va - vb) / max(floor, abs(va), abs(vb)))
    return worst


def _check(dev: float, what: str) -> float:
    if not dev <= RTOL:
        raise Mismatch(f"{what}: max relative deviation {dev!r} > {RTOL}")
    return dev


def _timed(fn):
    """(first-call seconds, steady-state seconds, steady result): the
    first call compiles, the second reuses the compiled program."""
    t0 = time.perf_counter()
    fn()
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    return t_first, time.perf_counter() - t0, out


def _cells(t_first: float, t_steady: float, n: int, dev: float,
           what: str) -> dict:
    return {"cells": n, "wall_s": t_steady, "compile_s": t_first - t_steady,
            "cells_per_s": n / t_steady, "max_rel_dev": _check(dev, what)}


def _knob_slice(res, idx: list[int]):
    """A ``BatchResult`` cut down to the knob indices ``idx``."""
    def cut(a):
        return a[..., idx]
    return dataclasses.replace(
        res, knob_grid=tuple(res.knob_grid[i] for i in idx),
        runtime_s=cut(res.runtime_s),
        **{f: {c: cut(v) for c, v in getattr(res, f).items()}
           for f in ("static_j", "dynamic_j", "wake_events", "gated_s",
                     "setpm_by")})


# --------------------------------------------------------------------------
# phases (each takes its sizes; main runs them at full size)
# --------------------------------------------------------------------------

def sweep_phase(workloads, npus, grid: KnobGrid, subsample: int) -> dict:
    """``sweep_grid`` on jax over the whole grid, then every
    ``subsample``-th knob's records against the numpy kernel."""
    t_first, t_steady, res = _timed(lambda: sweep_grid(
        workloads, npus, POLICIES, grid=grid, backend="jax",
        as_records=False))
    knobs = grid.product()
    idx = list(range(0, len(knobs), subsample))
    ref = evaluate_batch(workloads, npus, POLICIES,
                         [knobs[i] for i in idx], backend="numpy")
    dev = max_rel_dev(ref.records(), _knob_slice(res, idx).records())
    n = int(res.runtime_s.size)
    return dict(_cells(t_first, t_steady, n, dev, "sweep"),
                checked_cells=n // len(knobs) * len(idx))


def program_plane_phase(workloads, npus, grid: KnobGrid) -> dict:
    """``sweep_program_plane`` on jax against the numpy backend."""
    t_first, t_steady, got = _timed(lambda: sweep_program_plane(
        workloads, npus, grid, backend="jax"))
    ref = sweep_program_plane(workloads, npus, grid, backend="numpy")
    return _cells(t_first, t_steady, len(got),
                  max_rel_dev(ref, got, **PLANE_CMP), "program plane")


def fleet_phase(scenario, grid: KnobGrid) -> dict:
    """``sweep_fleet`` on jax, unguarded, against the numpy run."""
    t_first, t_steady, rep = _timed(lambda: sweep_fleet(
        scenario, grid, backend="jax", guard=None))
    if rep.guard is not None:
        raise Mismatch(f"fleet report carries guard events: {rep.guard}")
    ref = sweep_fleet(scenario, grid, backend="numpy", guard=None)
    dev = max_rel_dev(ref.summary, rep.summary)
    return {"epochs": rep.n_epochs, "requests": rep.requests_total,
            "wall_s": t_steady, "compile_s": t_first - t_steady,
            "epochs_per_s": rep.n_epochs / t_steady,
            "max_rel_dev": _check(dev, "fleet")}


@contextlib.contextmanager
def _result_devices(bk):
    """Collect, per kernel call, the devices holding the kernel's
    results: every jax kernel's outputs pass through ``bk.block``. An
    SPMD program's results sit on the devices it ran across."""
    seen: list[frozenset] = []
    block = bk.block

    def spy(tree):
        seen.append(frozenset().union(*(
            leaf.sharding.device_set
            for leaf in jax.tree_util.tree_leaves(tree))))
        return block(tree)

    bk.block = spy
    try:
        yield seen
    finally:
        del bk.block


def _require_span(seen: list, mesh, what: str) -> None:
    want = frozenset(mesh.devices.flat)
    if len(want) != mesh.devices.size or want not in seen:
        raise Mismatch(f"{what}: no kernel result spans the "
                       f"{mesh.devices.size} mesh devices "
                       f"(saw {[len(s) for s in seen]})")


def mesh_phase(workloads, npus, knobs, plane_workloads, plane_npus,
               plane_knobs, n_dev: int = 4) -> dict[str, dict]:
    """The ``jax_mesh=`` paths over ``n_dev`` devices, each against the
    single-device jax run (timed too) and the numpy kernel, each
    required to lay its kernel results on all ``n_dev`` devices."""
    if len(jax.devices()) < n_dev:
        raise RuntimeError(f"mesh phase needs {n_dev} devices, found "
                           f"{len(jax.devices())}")
    bk = get_backend("jax")

    def sweep(mesh):
        return evaluate_batch(workloads, npus, POLICIES, knobs,
                              backend="jax", jax_mesh=mesh)

    def plane(mesh):
        return program_plane_batch(plane_workloads, plane_npus,
                                   plane_knobs, backend="jax",
                                   jax_mesh=mesh)

    ref = {sweep: evaluate_batch(workloads, npus, POLICIES, knobs,
                                 backend="numpy").records(),
           plane: sweep_program_plane(plane_workloads, plane_npus,
                                      plane_knobs, backend="numpy")}
    cmp = {sweep: {}, plane: PLANE_CMP}
    runs = (("sweep 1 device", sweep, None),
            (f"gspmd wl={n_dev}", sweep, jax_compat.sweep_mesh(wl=n_dev)),
            (f"shard_map wl={n_dev // 2} knob=2", sweep,
             jax_compat.sweep_mesh(wl=n_dev // 2, knob=2)),
            ("program plane 1 device", plane, None),
            (f"program plane wl={n_dev}", plane,
             jax_compat.sweep_mesh(wl=n_dev)))
    one, out = {}, {}
    for name, run, mesh in runs:
        with _result_devices(bk) as seen:
            t_first, t_steady, res = _timed(lambda: run(mesh))
        got = res.records()
        dev = max_rel_dev(ref[run], got, **cmp[run])
        if mesh is None:
            one[run] = got
        else:
            _require_span(seen, mesh, name)
            dev = max(dev, max_rel_dev(one[run], got, **cmp[run]))
        out[name] = _cells(t_first, t_steady, len(got), dev, name)
    return out


# --------------------------------------------------------------------------
# full-size runs
# --------------------------------------------------------------------------

def sweep_workloads() -> list:
    return paper_suite() + [arch_workload(get_arch("qwen2.5-14b"),
                                          SHAPES["decode_32k"])]


def fleet_scenario(n_epochs: int = FLEET_EPOCHS):
    sc = build_scenario()
    return dataclasses.replace(sc, duration_s=n_epochs * sc.epoch_s)


def _line(kind: str, name: str, r: dict) -> str:
    rate = ("epochs_per_s", "epochs") if "epochs_per_s" in r \
        else ("cells_per_s", "cells")
    return (f"[{kind}] {name}: {r[rate[1]]} {rate[1]}, wall_s="
            f"{r['wall_s']!r}, compile_s={r['compile_s']!r}, "
            f"{rate[0]}={r[rate[0]]!r}, max_rel_dev={r['max_rel_dev']!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the jax_mesh= paths over 4 chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but jax's first device is "
              f"platform {dev.platform!r} ({dev.device_kind})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    kind = dev.device_kind
    cache = jax_compat.use_compile_cache()
    n_cached = sum(len(files) for _, _, files in os.walk(cache))
    print(f"[{kind}] compile cache {cache}: {n_cached} files before "
          f"this run", flush=True)

    if args.chips == 4:
        res = mesh_phase(
            paper_suite(), tuple(NPUS),
            KnobGrid(**SWEEP_AXES).product()[::SWEEP_SUBSAMPLE],
            paper_suite(), PLANE_NPUS, PLANE_GRID.product(), n_dev=4)
        for name, r in res.items():
            print(_line(kind, name, r))
    else:
        print(_line(kind, "sweep", sweep_phase(
            sweep_workloads(), tuple(NPUS), KnobGrid(**SWEEP_AXES),
            SWEEP_SUBSAMPLE)), flush=True)
        print(_line(kind, "program plane", program_plane_phase(
            paper_suite(), PLANE_NPUS, PLANE_GRID)), flush=True)
        print(_line(kind, "fleet", fleet_phase(
            fleet_scenario(), FLEET_GRID)), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
