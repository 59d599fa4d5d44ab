"""Entry ``program_plane``: software-managed gating of a fixed deployment
under a sensitivity study.

The deployment's workloads are traced once at set-up; each query runs
``program_plane_batch(..., backend="jax")`` over NPUs x the query's knob
grid and assembles the records with ``.records()``: the two calls
``sweep_program_plane`` makes. One record per (workload, npu, knob),
workload-major. Only ``delay_scale`` moves the event streams, so a fixed
``delay_scale`` axis keeps the event-scan kernel's shapes fixed.
"""
from __future__ import annotations

from bench.entries import common
from bench.generator import knob_points
from bench.reference import opgen, plane

# executor integers and setpm/wake counts exact; every other float field
# within LIMIT, relative to the larger magnitude or 1.0
EXACT = ("prog_", "n_events", "stall_", "wakes_prog", "setpm_prog")
FLOOR = 1.0
LIMIT = 1e-9


class Entry:
    def __init__(self, config: dict, traffic: dict):
        from repro.core.policies import KnobGrid
        from repro.core.program_plane import program_plane_batch
        self.arch = common.arch_config(config)
        self._grid = KnobGrid
        self._plane = program_plane_batch
        self._wls = None

    def build(self, q: dict):
        if self._wls is None:         # the deployment is fixed: trace once
            self._wls = common.workloads(self.arch, q)
        return self._wls

    def call(self, q: dict, wls):
        return self._plane(wls, tuple(q["npus"]),
                           self._grid(**q["axes"]).product(),
                           backend="jax")

    @staticmethod
    def records(res) -> list[dict]:
        return res.records()

    @staticmethod
    def size(q: dict) -> int:
        return (len(q["workloads"]) * len(q["npus"])
                * len(knob_points(q["axes"])))


def reference(config: dict, q: dict, indices, f=float) -> list[dict]:
    """The reference's records at ``indices`` of query ``q``."""
    arch = config["arch"]
    knobs = knob_points(q["axes"])
    a_n, k_n = len(q["npus"]), len(knobs)
    traces = {}
    out = []
    for i in indices:
        wi, rest = divmod(i, a_n * k_n)
        ai, ki = divmod(rest, k_n)
        w = q["workloads"][wi]
        if wi not in traces:
            traces[wi] = opgen.trace(arch, w["kind"], w["seq_len"],
                                     w["global_batch"], w["n_chips"],
                                     w["tp"])
        out.append(plane.record(w["name"], traces[wi], q["npus"][ai],
                                knobs[ki], ki, f))
    return out
