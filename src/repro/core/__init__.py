"""The paper's contribution: ReGate power-gating co-design.

hw/power      — NPU-A..E specs (Table 2/3) + calibrated power model
sa_gating     — PE-level spatial SA gating (Figs 10-13)
isa/passes    — setpm ISA extension + compiler passes (Figs 14-15, §4.3)
opgen/policies— operator traces, columnar trace compilation, and the five
                designs (§6): the scalar ``evaluate_reference`` oracle
                and one backend-neutral sweep kernel that
                ``evaluate``, ``evaluate_batch`` and every plane use
sweep         — batched design-space sweeps (workloads × npus × policies
                × knob grids): one ``evaluate_batch`` pass over the
                stacked super-trace; ``sweep_reference`` loop oracle;
                ``sweep_grid`` fine-knob §6.5 grids (100k-cell scale)
backend       — pluggable array substrate for the sweep kernel: numpy
                (eager, on the host) or one jitted float64 jax program
                (reused across NPU generations)
carbon        — operational/embodied carbon (Figs 24-25)
slo           — SLO-constrained config sweep (Fig 2)
hlo/roofline  — compiled-HLO cost extraction for the dry-run
ici_topology  — ring / 2-D-mesh collective schedules lowered onto the
                op-level trace (per-step ICI busy/idle timelines)
perturb       — seeded fault injection + adversarial perturbation
                (jitter plane): burst compression, link degradation,
                stragglers, idle fragmentation, clock jitter; the
                ISA differential fuzz harness
"""
from repro.core.backend import (default_backend, get_backend,
                                set_default_backend)
from repro.core.hw import NPUS, TARGET, get_npu
from repro.core.opgen import compile_trace, stack_traces
from repro.core.policies import POLICIES, evaluate, evaluate_all, \
    evaluate_batch, evaluate_reference, savings_vs_nopg
from repro.core.sweep import knob_product, sweep, sweep_grid, \
    sweep_reference, sweep_robustness

__all__ = ["NPUS", "TARGET", "get_npu", "POLICIES", "compile_trace",
           "stack_traces", "evaluate", "evaluate_all", "evaluate_batch",
           "evaluate_reference", "savings_vs_nopg", "sweep",
           "sweep_grid", "sweep_reference", "sweep_robustness",
           "knob_product", "get_backend", "set_default_backend",
           "default_backend"]
