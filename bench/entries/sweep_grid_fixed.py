"""Entry ``sweep_grid_fixed``: ``sweep_grid`` over one fixed deployment,
its knobs drawn afresh every query.

The configuration's shapes are traced once, at set-up, at their
published batch, and every query sweeps those same ``Workload``
objects: ``stack_traces``' identity cache and the stack's device
columns (``policies._backend_data``) hit, so a query pays the knob
columns, the kernel, the harvest and the record assembly, and neither
opgen, the host columns nor the stack's put. The traffic file draws no
``batch_fractions``, so its queries name these workloads, and the
records are checked against ``sweep_grid``'s reference.
"""
from __future__ import annotations

from bench.entries import common, sweep_grid

EXACT = sweep_grid.EXACT
FLOOR = sweep_grid.FLOOR
LIMIT = sweep_grid.LIMIT
reference = sweep_grid.reference


class Entry(sweep_grid.Entry):
    def __init__(self, config: dict, traffic: dict):
        super().__init__(config, traffic)
        if traffic.get("batch_fractions"):
            raise ValueError("sweep_grid_fixed sweeps the published batch: "
                             "its traffic draws no batch_fractions")
        self._wls = common.workloads(self.arch, {"workloads": [
            dict(sh, shape=name) for name, sh in config["shapes"].items()]})

    def build(self, q: dict):
        self._stack(self._wls)
        return self._wls
