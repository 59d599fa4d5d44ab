"""Entry ``sweep_grid_hybrid``: ``sweep_grid`` for models whose blocks
are of different kinds, in a published layer pattern (Mamba-2,
attention and mixture-of-experts blocks).

The program path is ``sweep_grid``'s, with the configuration's ``moe``
block and its ``layer_pattern`` built into the model configuration; the
records are checked against ``bench/reference/hybrid.py``'s traces,
priced by the same reference engine, under the same limits.

A shape's trace can have more rows at one batch fraction than at
another (the routed experts split into two row groups where the token
slots do not divide evenly), and the sweep compiles once per stack
shape. So set-up sweeps one stack of every shape the traffic can draw,
and nothing compiles inside the window.
"""
from __future__ import annotations

import dataclasses
import itertools

from bench import generator
from bench.entries import common, sweep_grid
from bench.generator import knob_points
from bench.reference import engine, hybrid

EXACT = sweep_grid.EXACT
FLOOR = sweep_grid.FLOOR
LIMIT = sweep_grid.LIMIT


def _with_moe(arch):
    """``common.arch_config``'s ``ArchConfig`` (its ``ssm`` built) with
    its ``moe`` block built too."""
    from repro.configs.base import MoEConfig
    return dataclasses.replace(arch, moe=MoEConfig(**arch.moe))


def arch_config(config: dict):
    """``ArchConfig`` from the file's ``arch`` block, with its ``ssm``
    and ``moe`` sub-configs."""
    return _with_moe(common.arch_config(config))


def _stack_shape(wl) -> tuple:
    """What the compiled sweep of a stack depends on, for one of its
    workloads: its op rows and which units each row keeps busy (the
    gap indices follow from those)."""
    return tuple((o.name, o.flops_sa > 0, o.flops_vu > 0, o.bytes_hbm > 0,
                  o.bytes_ici > 0) for o in wl.ops)


class Entry(sweep_grid.Entry):
    def __init__(self, config: dict, traffic: dict):
        super().__init__(config, traffic)
        self.arch = _with_moe(self.arch)
        # a program that does not trace the model's blocks as the
        # reference does cannot run the cell: it is refused here, at set-up
        for name, sh in config["shapes"].items():
            (wl,) = common.workloads(self.arch, {"workloads": [dict(
                sh, shape=name, global_batch=1)]})
            ref = hybrid.trace(config["arch"], sh["kind"], sh["seq_len"], 1,
                               sh["n_chips"], sh["tp"])
            if [o.name for o in wl.ops] != [o["name"] for o in ref]:
                raise RuntimeError(
                    f"the program's {name} trace of {self.arch.name} lacks "
                    f"the reference's ops (the layer pattern's Mamba-2, "
                    f"attention and relu2 expert blocks)")
        for q in self.warm_queries(config, traffic):
            self.call(q, self.build(q))

    def warm_queries(self, config: dict, traffic: dict) -> list[dict]:
        """One query for every stack shape the traffic's batch fractions
        can draw: each shape's distinct traces, crossed."""
        per_frac = [generator.query(config, dict(traffic, batch_fractions=[f]),
                                    0, 0)
                    for f in traffic["batch_fractions"]]
        options = []
        for i in range(len(config["shapes"])):
            seen: dict = {}
            for q in per_frac:
                w = q["workloads"][i]
                (wl,) = common.workloads(self.arch, {"workloads": [w]})
                seen.setdefault(_stack_shape(wl), w)
            options.append(list(seen.values()))
        return [dict(per_frac[0], workloads=list(ws))
                for ws in itertools.product(*options)]


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
            traces[wi] = hybrid.trace(arch, w["kind"], w["seq_len"],
                                      w["global_batch"], w["n_chips"],
                                      w["tp"])
        out.append(engine.record(w["name"], traces[wi], q["npus"][ai],
                                 q["policies"][pi], knobs[ki], ki, f))
    return out
