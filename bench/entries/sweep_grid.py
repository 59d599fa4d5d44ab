"""Entry ``sweep_grid``: a design-space sweep over fresh deployments.

Each query traces its workloads anew (``opgen.arch_workload`` and
``stack_traces``), sweeps them over NPUs x policies x the knob grid with
``sweep_grid(..., backend="jax", as_records=False)`` and assembles the
records with ``BatchResult.records()``: the two calls ``sweep_grid``
makes with ``as_records=True``. Records run workload-major, then NPU,
then policy, then knob.
"""
from __future__ import annotations

from bench.entries import common
from bench.generator import knob_points
from bench.reference import engine, opgen

# every float field within LIMIT of the reference, relative to the larger
# magnitude (floor 1e-30); labels, knob columns and counts exact
EXACT = ()
FLOOR = 1e-30
LIMIT = 1e-9


class Entry:
    def __init__(self, config: dict, traffic: dict):
        from repro.core.opgen import stack_traces
        from repro.core.policies import KnobGrid
        from repro.core.sweep import sweep_grid
        self.arch = common.arch_config(config)
        self._stack = stack_traces
        self._grid = KnobGrid
        self._sweep = sweep_grid

    def build(self, q: dict):
        wls = common.workloads(self.arch, q)
        self._stack(wls)
        return wls

    def call(self, q: dict, wls):
        return self._sweep(wls, tuple(q["npus"]), tuple(q["policies"]),
                           grid=self._grid(**q["axes"]), backend="jax",
                           as_records=False)

    @staticmethod
    def records(res) -> list[dict]:
        return res.records()

    @staticmethod
    def size(q: dict) -> int:
        return (len(q["workloads"]) * len(q["npus"]) * len(q["policies"])
                * len(knob_points(q["axes"])))


def reference(config: dict, q: dict, indices, f=float) -> list[dict]:
    """The reference's records at ``indices`` of query ``q``."""
    arch = config["arch"]
    knobs = knob_points(q["axes"])
    a_n, p_n, k_n = len(q["npus"]), len(q["policies"]), len(knobs)
    traces = {}
    out = []
    for i in indices:
        wi, rest = divmod(i, a_n * p_n * k_n)
        ai, rest = divmod(rest, p_n * k_n)
        pi, ki = divmod(rest, k_n)
        w = q["workloads"][wi]
        if wi not in traces:
            traces[wi] = opgen.trace(arch, w["kind"], w["seq_len"],
                                     w["global_batch"], w["n_chips"],
                                     w["tp"])
        out.append(engine.record(w["name"], traces[wi], q["npus"][ai],
                                 q["policies"][pi], knobs[ki], ki, f))
    return out

