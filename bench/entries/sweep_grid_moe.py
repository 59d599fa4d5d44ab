"""Entry ``sweep_grid_moe``: ``sweep_grid`` for models with latent
attention and DeepSeekMoE.

The program path is ``sweep_grid``'s, with the configuration's ``mla``
and ``moe`` blocks built into the model configuration; the records are
checked against ``bench/reference/mla_moe.py``'s traces, priced by the
same reference engine, under the same limits.
"""
from __future__ import annotations

from bench.entries import common, sweep_grid
from bench.generator import knob_points
from bench.reference import engine, mla_moe

EXACT = sweep_grid.EXACT
FLOOR = sweep_grid.FLOOR
LIMIT = sweep_grid.LIMIT


def arch_config(config: dict):
    """``ArchConfig`` from the file's ``arch`` block, with its ``mla``
    and ``moe`` sub-configs."""
    from repro.configs.base import ArchConfig, MLAConfig, MoEConfig
    arch = dict(config["arch"])
    arch["mla"] = MLAConfig(**arch["mla"])
    arch["moe"] = MoEConfig(**arch["moe"])
    return ArchConfig(**arch)


class Entry(sweep_grid.Entry):
    def __init__(self, config: dict, traffic: dict):
        super().__init__(config, traffic)
        self.arch = arch_config(config)
        # a program that does not trace the model's ops as the reference
        # does cannot run the cell: it is refused here, at set-up
        name, sh = next(iter(config["shapes"].items()))
        (wl,) = common.workloads(self.arch, {"workloads": [dict(
            sh, shape=name, global_batch=1)]})
        ref = mla_moe.trace(config["arch"], sh["kind"], sh["seq_len"], 1,
                            sh["n_chips"], sh["tp"])
        if [o.name for o in wl.ops] != [o["name"] for o in ref]:
            raise RuntimeError(
                f"the program's {name} trace of {self.arch.name} lacks "
                f"the reference's ops (latent attention, routed experts)")


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
            traces[wi] = mla_moe.trace(arch, w["kind"], w["seq_len"],
                                       w["global_batch"], w["n_chips"],
                                       w["tp"])
        out.append(engine.record(w["name"], traces[wi], q["npus"][ai],
                                 q["policies"][pi], knobs[ki], ki, f))
    return out
