"""Power-gating policy engine — the simulator backend (paper §4, §6).

Evaluates a Workload trace on an NPUSpec under one of five designs:

* ``NoPG``        — no power gating (baseline).
* ``ReGate-Base`` — conventional HW idle-detection at component granularity
                    (detection window = BET/3); the SA gates as a whole;
                    SRAM can only SLEEP (hardware can't prove a segment
                    dead); exposed wake-up delays extend the runtime.
* ``ReGate-HW``   — + PE-level spatial SA gating (row/col zero-weight
                    prefix bitmaps + diagonal PE_on propagation): SA static
                    follows ``sa_gating.gating_stats`` occupancy, exposed SA
                    wake drops to a single PE delay.
* ``ReGate-Full`` — + SW-managed VU & SRAM via ``setpm``: exact idle
                    intervals (no detection window waste), wakes hidden by
                    the compiler, unused SRAM segments fully OFF.
* ``Ideal``       — zero leakage when gated, zero delays, every idle cycle
                    gated (roofline).

Timing model: per op, each component is active for its own service time;
op duration = max over components (perfect overlap); ops run back-to-back.
Idle intervals per component are the within-op slack plus whole ops where
the component is unused, merged across op boundaries.

One oracle and one engine price these semantics:

* ``evaluate_reference`` — the original pure-Python per-op loop, kept as
  the oracle; the equivalence tests hold the engine to ≤1e-9 relative
  on every EnergyReport field.
* ``_sweep_kernel`` — one backend-neutral program over the whole
  (workload × npu × policy × knob) cross product. ``evaluate_batch``
  stacks every workload trace into one ragged super-trace
  (``opgen.stack_traces``) and runs the kernel once per NPU on the
  chosen backend (``repro.core.backend``): eagerly on the host with
  ``backend="numpy"``, or as one ``jax.jit``-compiled float64 program
  with ``backend="jax"``. Gap merging uses a host-built fixed-shape
  chunk index, per-NPU numbers enter as arrays so one compiled program
  serves every generation, and the per-op service times and SA
  PE-occupancy math run inside the kernel (``bk.sa_occupancy``), so SA
  width is a real ``PolicyKnobs.sa_width`` knob axis. Heavy O(n_ops)
  work runs once per unique SA width and unique (width, delay, window)
  triple, with the leakage knobs folded in linearly afterwards; results
  are memoized per distinct component policy. ``evaluate`` is the
  one-cell call of ``evaluate_batch`` on numpy, and every plane
  (sweeps, program plane, fleet) goes through ``evaluate_batch``. A
  ``jax_mesh`` scales the jax program out across devices — GSPMD
  op-axis sharding on a ``("wl",)`` mesh, or an explicit ``shard_map``
  SPMD program when the mesh has a ``"knob"`` axis.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from repro.core import backend as backend_mod
from repro.core.backend import (gap_index, get_backend, put_slabs,
                                transfer_counts, unpack_slabs)
from repro.core.hw import NPUSpec, get_npu, with_sa_width
from repro.core.opgen import (Op, StackedTrace, TraceArrays, Workload,
                              stack_traces)
from repro.core.power import COMPONENTS, PowerModel
from repro.core.sa_gating import SAStats, gating_stats, gating_stats_batch

POLICIES = ("NoPG", "ReGate-Base", "ReGate-HW", "ReGate-Full", "Ideal")

GATEABLE = ("sa", "vu", "sram", "hbm", "ici")


@dataclass(frozen=True)
class PolicyKnobs:
    """Sensitivity-analysis overrides (paper §6.5).

    ``sa_width`` overrides the NPU's systolic-array width (``None`` →
    native). It is a real knob axis: the scalar oracle evaluates on a
    memoized ``hw.with_sa_width`` variant spec, and the sweep kernel
    carries the width as an array value (traced on jax) so one
    compiled program serves the whole width axis. Note SA peak FLOP/s
    is derived from the width, so this axis moves throughput and
    occupancy together — the paper's §6.5 width sensitivity, without
    per-width NPU variants.
    """
    leak_off_logic: Optional[float] = None
    leak_sram_sleep: Optional[float] = None
    leak_sram_off: Optional[float] = None
    delay_scale: float = 1.0  # scales wake-up delays and BETs
    sa_width: Optional[int] = None
    # Scales ONLY the HW idle-detection window (paper default BET/3),
    # leaving wake-up delays and BETs alone — the genuine detection-
    # threshold axis for the jitter-plane robustness sweep.
    window_scale: float = 1.0


def _knob_axis(name: str, values) -> tuple:
    """Coerce one ``KnobGrid`` axis to a validated tuple. A bare scalar
    (including ``None``) is a one-point axis."""
    if values is None or np.isscalar(values):
        values = (values,)
    axis = tuple(values)
    if not axis:
        raise ValueError(f"KnobGrid axis {name!r} must be non-empty")
    for v in axis:
        if name in ("delay_scale", "window_scale"):
            if v is None or not (np.isfinite(v) and v > 0):
                raise ValueError(
                    f"KnobGrid axis {name!r}: values must be finite and "
                    f"> 0, got {v!r}")
        elif name == "sa_width":
            if v is not None and not (float(v).is_integer()
                                      and int(v) >= 1):
                raise ValueError(
                    f"KnobGrid axis {name!r}: values must be None or "
                    f"an integer >= 1, got {v!r}")
        else:  # leakage fractions
            if v is not None and not (np.isfinite(v) and v >= 0):
                raise ValueError(
                    f"KnobGrid axis {name!r}: values must be None or "
                    f"finite and >= 0, got {v!r}")
    return axis


@dataclass(frozen=True)
class KnobGrid:
    """The §6.5 sensitivity axes as one first-class object (ISSUE 7).

    Replaces the six parallel kwargs that used to be repeated across
    ``knob_product`` / ``sweep_grid`` / ``sweep_robustness``: each field
    is one axis (a bare scalar is a one-point axis; ``None`` entries
    mean the per-NPU Table 3 default, and ``sa_width=None`` the
    generation's native width), validated at construction, and
    ``product()`` crosses them into the flat ``PolicyKnobs`` grid in
    the canonical knob ordering — ``sa_width`` outermost, then
    ``window_scale``, then ``delay_scale``, ``leak_off_logic``,
    ``leak_sram_sleep``, ``leak_sram_off`` innermost (byte-identical to
    the legacy ``knob_product`` ordering, so record tables and
    ``knob_idx`` values are unchanged). All sweep entry points
    (``sweep`` / ``sweep_grid`` / ``evaluate_batch`` / ``sweep_fleet``)
    accept a ``KnobGrid`` wherever they accept a knob sequence.
    """

    delay_scale: Sequence[float] = (1.0,)
    leak_off_logic: Sequence[Optional[float]] = (None,)
    leak_sram_sleep: Sequence[Optional[float]] = (None,)
    leak_sram_off: Sequence[Optional[float]] = (None,)
    sa_width: Sequence[Optional[int]] = (None,)
    window_scale: Sequence[float] = (1.0,)

    #: record-table column names for the knob axes (with ``knob_idx``
    #: these are the columns every sweep record carries unconditionally)
    COLUMNS = ("delay_scale", "leak_off_logic", "leak_sram_sleep",
               "leak_sram_off", "sa_width", "window_scale")

    def __post_init__(self):
        for name in self.COLUMNS:
            object.__setattr__(self, name,
                               _knob_axis(name, getattr(self, name)))

    @classmethod
    def columns(cls) -> tuple[str, ...]:
        """The knob column names emitted into every sweep record."""
        return cls.COLUMNS

    @property
    def size(self) -> int:
        n = 1
        for name in self.COLUMNS:
            n *= len(getattr(self, name))
        return n

    def product(self) -> list[PolicyKnobs]:
        """Cross the axes into the flat knob grid (canonical order)."""
        return [PolicyKnobs(delay_scale=d, leak_off_logic=lo,
                            leak_sram_sleep=ls, leak_sram_off=lf,
                            sa_width=sw, window_scale=w)
                for sw in self.sa_width for w in self.window_scale
                for d in self.delay_scale
                for lo in self.leak_off_logic
                for ls in self.leak_sram_sleep
                for lf in self.leak_sram_off]


def as_knob_tuple(knob_grid) -> tuple[PolicyKnobs, ...]:
    """Normalize any accepted knob-grid spelling — ``None`` (the single
    default knob point), a ``KnobGrid``, or a sequence of
    ``PolicyKnobs`` — to the flat tuple the batched engines consume."""
    if knob_grid is None:
        return (PolicyKnobs(),)
    if isinstance(knob_grid, KnobGrid):
        return tuple(knob_grid.product())
    return tuple(knob_grid)


def knob_columns(knobs: PolicyKnobs, knob_idx: int) -> dict:
    """The knob columns of one sweep record (``knob_idx`` + every
    ``KnobGrid.columns()`` entry, emitted unconditionally so record
    consumers like ``sweep.with_savings``/``sweep.group_by`` never see
    a missing axis)."""
    rec = {"knob_idx": int(knob_idx)}
    for name in KnobGrid.COLUMNS:
        rec[name] = getattr(knobs, name)
    return rec


@dataclass
class EnergyReport:
    workload: str
    policy: str
    npu: str
    runtime_s: float
    static_j: dict[str, float]
    dynamic_j: dict[str, float]
    setpm_count: float = 0.0
    wake_events: dict[str, float] = field(default_factory=dict)
    # per-component time spent power-gated, in seconds (sram: unused-
    # capacity-weighted seconds, i.e. capacity_fraction x time integral);
    # temporal gating only — SA spatial PE-gating is tracked separately
    # through sa_gating occupancy
    gated_s: dict[str, float] = field(default_factory=dict)
    # per-component setpm instruction counts (sums to setpm_count)
    setpm_by: dict[str, float] = field(default_factory=dict)

    @property
    def total_j(self) -> float:
        return sum(self.static_j.values()) + sum(self.dynamic_j.values())

    @property
    def avg_power_w(self) -> float:
        return self.total_j / max(1e-12, self.runtime_s)

    @property
    def static_frac(self) -> float:
        return sum(self.static_j.values()) / max(1e-12, self.total_j)

    def setpm_per_1k_cycles(self, npu: NPUSpec) -> float:
        return self.setpm_count / max(1.0, npu.cycles(self.runtime_s)) * 1e3


# --------------------------------------------------------------------------
# per-op component service times
# --------------------------------------------------------------------------

def op_times(op: Op, npu: NPUSpec) -> dict[str, float]:
    eff = 1.0
    stats: Optional[SAStats] = None
    if op.flops_sa > 0 and op.matmul_dims is not None:
        stats = gating_stats(*op.matmul_dims, npu.sa_width)
        # achieved throughput scales with ON-PE occupancy
        flops_cycles = op.matmul_dims[0] * op.matmul_dims[1] \
            * op.matmul_dims[2] / (npu.sa_width ** 2)
        eff = min(1.0, flops_cycles / max(1e-9, stats.duration_cycles))
        eff = max(eff, 1e-3)
    t = {
        "sa": op.flops_sa / (npu.sa_flops * eff) if op.flops_sa else 0.0,
        "vu": op.flops_vu / npu.vu_flops if op.flops_vu else 0.0,
        "hbm": op.bytes_hbm / npu.hbm_bw if op.bytes_hbm else 0.0,
        "ici": op.bytes_ici / npu.ici_bw if op.bytes_ici else 0.0,
    }
    dur = max(max(t.values()), 1e-12)
    t["sram"] = dur  # SRAM serves whoever is active
    t["other"] = dur
    t["_dur"] = dur
    t["_sa_eff"] = eff
    return t


# --------------------------------------------------------------------------
# policy semantics per component
# --------------------------------------------------------------------------

def _gated_idle_energy(gap_s: float, p_static: float, *, mode: str,
                       bet_s: float, delay_s: float, window_s: float,
                       leak: float) \
        -> tuple[float, float, float, float, float]:
    """Energy spent during one idle interval of length ``gap_s``.

    Returns (energy_J, exposed_wake_s, wake_events, setpm_count,
    gated_s). mode: "none" | "hw" | "sw" | "ideal".
    """
    if gap_s <= 0:
        return 0.0, 0.0, 0.0, 0.0, 0.0
    if mode == "none":
        return p_static * gap_s, 0.0, 0.0, 0.0, 0.0
    if mode == "ideal":
        return 0.0, 0.0, 0.0, 0.0, gap_s
    if mode == "hw":
        # observe for the detection window, then gate if still idle;
        # next use pays the exposed wake-up delay.
        if gap_s <= window_s:
            return p_static * gap_s, 0.0, 0.0, 0.0, 0.0
        gated = gap_s - window_s
        e = p_static * window_s + leak * p_static * gated \
            + p_static * delay_s  # transition energy (on/off ramp)
        return e, delay_s, 1.0, 0.0, gated
    # sw: compiler knows the interval; gate only if profitable & hideable
    if gap_s >= max(bet_s, 2.0 * delay_s):
        e = leak * p_static * (gap_s - 2 * delay_s) \
            + p_static * 2 * delay_s
        # setpm off + setpm on; 2x delay held at full power (transition)
        return e, 0.0, 1.0, 2.0, gap_s - 2 * delay_s
    return p_static * gap_s, 0.0, 0.0, 0.0, 0.0


@dataclass(frozen=True)
class _CompPolicy:
    mode: str          # none | hw | sw | ideal
    delay_key: str     # key into gating tables
    spatial_sa: bool = False
    sram_state: str = "on"  # on | sleep | off | ideal (unused-capacity)


def _component_policies(policy: str) -> dict[str, _CompPolicy]:
    if policy == "NoPG":
        return {c: _CompPolicy("none", "") for c in COMPONENTS}
    if policy == "Ideal":
        d = {c: _CompPolicy("ideal", "", spatial_sa=True,
                            sram_state="ideal") for c in COMPONENTS}
        d["other"] = _CompPolicy("none", "")
        return d
    base = {
        "sa": _CompPolicy("hw", "sa_full"),
        "vu": _CompPolicy("hw", "vu"),
        "hbm": _CompPolicy("hw", "hbm"),
        "ici": _CompPolicy("hw", "ici"),
        "sram": _CompPolicy("hw", "sram_sleep", sram_state="sleep"),
        "other": _CompPolicy("none", ""),
    }
    if policy == "ReGate-Base":
        return base
    if policy == "ReGate-HW":
        base["sa"] = _CompPolicy("hw", "sa_pe", spatial_sa=True)
        return base
    if policy == "ReGate-Full":
        base["sa"] = _CompPolicy("hw", "sa_pe", spatial_sa=True)
        base["vu"] = _CompPolicy("sw", "vu")
        base["sram"] = _CompPolicy("sw", "sram_off", sram_state="off")
        return base
    raise KeyError(policy)


# --------------------------------------------------------------------------
# evaluation — scalar reference engine (original per-op loop)
# --------------------------------------------------------------------------

def evaluate_reference(wl: Workload, npu: NPUSpec | str = "NPU-D",
                       policy: str = "ReGate-Full",
                       knobs: PolicyKnobs = PolicyKnobs()) -> EnergyReport:
    npu = get_npu(npu) if isinstance(npu, str) else npu
    npu = with_sa_width(npu, knobs.sa_width)
    pm = PowerModel(npu)
    g = npu.gating
    cp = _component_policies(policy)

    leak_logic = knobs.leak_off_logic if knobs.leak_off_logic is not None \
        else g.leak_off_logic
    leak_sleep = knobs.leak_sram_sleep if knobs.leak_sram_sleep is not None \
        else g.leak_sram_sleep
    leak_off = knobs.leak_sram_off if knobs.leak_sram_off is not None \
        else g.leak_sram_off

    def delay_s(key: str) -> float:
        return g.on_off_delay.get(key, 0) * knobs.delay_scale / npu.freq_hz

    def bet_s(key: str) -> float:
        return g.bet.get(key, 0) * knobs.delay_scale / npu.freq_hz

    static_w = pm.static_w
    dyn_w = pm.dyn_max_w

    static_j = {c: 0.0 for c in COMPONENTS}
    dynamic_j = {c: 0.0 for c in COMPONENTS}
    runtime = 0.0
    overhead = 0.0
    setpm_by = {c: 0.0 for c in COMPONENTS}
    gated = {c: 0.0 for c in COMPONENTS}
    wakes = {c: 0.0 for c in COMPONENTS}

    # pending idle gap per component (merged across ops)
    pending = {c: 0.0 for c in COMPONENTS}

    def close_gap(c: str):
        nonlocal overhead
        gap = pending[c]
        pending[c] = 0.0
        if gap <= 0:
            return
        pol = cp[c]
        # HBM auto-refresh is a FLOOR: the DRAM refresh burn does not
        # shrink when the logic threshold voltage changes (paper §6.5)
        leak = max(leak_logic, g.leak_hbm_refresh) if c == "hbm" \
            else leak_logic
        e, exposed, nw, sp, gs = _gated_idle_energy(
            gap, static_w[c], mode=pol.mode, bet_s=bet_s(pol.delay_key),
            delay_s=delay_s(pol.delay_key),
            window_s=bet_s(pol.delay_key) * g.detection_window_frac
            * knobs.window_scale,
            leak=leak)
        static_j[c] += e
        overhead_local = exposed
        if c in ("hbm", "ici"):
            # wake overlapped with the long DMA issue latency half the time
            overhead_local *= 0.5
        nonlocal_overhead(overhead_local)
        setpm_by[c] += sp
        gated[c] += gs
        wakes[c] += nw

    def nonlocal_overhead(x: float):
        nonlocal overhead
        overhead += x

    def fine_grained_vu(t_vu: float, dur: float, n: int):
        """VU slack inside a mixed op is fragmented into per-burst gaps
        (paper Fig 15): HW detection mostly cannot exploit them, SW setpm
        can. Returns nothing; mutates accumulators."""
        pol = cp["vu"]
        slack = dur - t_vu
        if slack <= 0:
            return
        active_cy = max(1.0, npu.cycles(t_vu))
        n_bursts = max(1.0, active_cy / g.vu_burst_cycles)
        gap_cy = npu.cycles(slack) / n_bursts
        bet_cy = g.bet["vu"] * knobs.delay_scale
        delay_cy = g.on_off_delay["vu"] * knobs.delay_scale
        window_cy = bet_cy * g.detection_window_frac * knobs.window_scale
        p = static_w["vu"]
        if pol.mode == "none":
            static_j["vu"] += p * slack * n
        elif pol.mode == "ideal":
            gated["vu"] += slack * n
        elif pol.mode == "hw":
            if gap_cy > bet_cy:
                gated_frac = max(0.0, (gap_cy - window_cy) / gap_cy)
                static_j["vu"] += p * slack * n * (
                    (1 - gated_frac) + leak_logic * gated_frac)
                gated["vu"] += slack * n * gated_frac
                # exposed wake per burst: Base/HW hardware cannot pre-wake
                nonlocal_overhead(n_bursts * delay_cy / npu.freq_hz * n)
                wakes["vu"] += n_bursts * n
            else:
                static_j["vu"] += p * slack * n
        else:  # sw
            if gap_cy >= max(bet_cy, 2 * delay_cy):
                trans = 2 * delay_cy / gap_cy
                static_j["vu"] += p * slack * n * (
                    trans + leak_logic * (1 - trans))
                gated["vu"] += slack * n * (1 - trans)
                setpm_by["vu"] += 2 * n_bursts * n
                wakes["vu"] += n_bursts * n
            else:
                static_j["vu"] += p * slack * n

    prev_used: Optional[float] = None  # sram setpm boundary tracking
    for op in wl.ops:
        t = op_times(op, npu)
        dur = t["_dur"]
        n = op.count
        for c in COMPONENTS:
            a = t[c] if c in t else 0.0
            if c in ("sram", "other"):
                a = dur  # handled below
            if a > 0:
                close_gap(c)

        # --- active-time static & dynamic energy (xN instances) ---
        for c in ("sa", "vu", "hbm", "ici"):
            a = t[c]
            if a <= 0:
                pending[c] += dur * n
                continue
            pol = cp[c]
            # dynamic: proportional to useful work
            if c == "sa":
                dynamic_j[c] += dyn_w[c] * (op.flops_sa / npu.sa_flops) * n
            else:
                dynamic_j[c] += dyn_w[c] * a * n
            # static during the active portion
            if c == "sa" and pol.spatial_sa and op.matmul_dims is not None:
                st = gating_stats(*op.matmul_dims, npu.sa_width)
                occ = (st.frac_on + g.leak_pe_weight_on * st.frac_w_on
                       + leak_logic * st.frac_off)
                if pol.mode == "ideal":
                    occ = st.frac_on
                static_j[c] += static_w[c] * occ * a * n
            else:
                static_j[c] += static_w[c] * a * n
            # within-op slack
            if c == "vu":
                fine_grained_vu(a, dur, n)
                continue
            slack = dur - a
            if slack > 0:
                leak = max(leak_logic, g.leak_hbm_refresh) if c == "hbm" \
                    else leak_logic
                e, exposed, nw, sp, gs = _gated_idle_energy(
                    slack, static_w[c], mode=pol.mode,
                    bet_s=bet_s(pol.delay_key),
                    delay_s=delay_s(pol.delay_key),
                    window_s=bet_s(pol.delay_key)
                    * g.detection_window_frac * knobs.window_scale,
                    leak=leak)
                static_j[c] += e * n
                ov = exposed * n
                if c in ("hbm", "ici"):
                    ov *= 0.5
                nonlocal_overhead(ov)
                setpm_by[c] += sp * n
                gated[c] += gs * n
                wakes[c] += nw * n

        # --- SRAM: capacity-proportional static, demand-gated remainder ---
        pol = cp["sram"]
        used = min(1.0, op.sram_demand / npu.sram_bytes)
        unused = 1.0 - used
        if pol.sram_state == "on":
            sram_leak_unused = 1.0
        elif pol.sram_state == "sleep":
            sram_leak_unused = leak_sleep
        elif pol.sram_state == "off":
            sram_leak_unused = leak_off
        else:  # ideal
            sram_leak_unused = 0.0
        static_j["sram"] += static_w["sram"] * dur * n * (
            used + unused * sram_leak_unused)
        if pol.sram_state != "on":
            gated["sram"] += unused * dur * n
        if pol.sram_state in ("sleep", "off") and pol.mode == "sw":
            # one range-setpm pair per demand-CHANGE boundary (Fig 14
            # variant 1 collapses contiguous segments; a boundary where
            # the footprint is unchanged needs no instruction), plus the
            # initial gate of the above-demand range
            if (used < 1.0 if prev_used is None else used != prev_used):
                setpm_by["sram"] += 2.0
        prev_used = used
        dynamic_j["sram"] += dyn_w["sram"] * max(
            t["sa"], t["vu"], t["hbm"], t["ici"]) * 0.5 * n

        # --- other: never gated ---
        static_j["other"] += static_w["other"] * dur * n
        dynamic_j["other"] += dyn_w["other"] * dur * 0.3 * n

        runtime += dur * n

    # close trailing gaps
    for c in COMPONENTS:
        close_gap(c)

    runtime += overhead
    return EnergyReport(
        workload=wl.name, policy=policy, npu=npu.name,
        runtime_s=runtime, static_j=static_j, dynamic_j=dynamic_j,
        setpm_count=sum(setpm_by.values()), wake_events=wakes,
        gated_s=gated, setpm_by=setpm_by)


# --------------------------------------------------------------------------
# per-op columns on the host: lowering, figures, the program plane's folds
# --------------------------------------------------------------------------

def trace_times(tr: TraceArrays, npu: NPUSpec) -> dict[str, np.ndarray]:
    """Per-op service-time arrays for one NPU (the columnar ``op_times``).

    Cached on the trace, keyed by NPUSpec identity (ad-hoc ``replace()``d
    specs may reuse a registry name with different hardware): times and
    SA-occupancy fractions depend only on the hardware, not on policy or
    knobs, so one computation serves every cell of a (policy × knobs)
    sweep.
    """
    hit = tr._derived.get(id(npu))
    if hit is not None and hit[0] is npu:
        return hit[1]
    n = tr.n_ops
    eff = np.ones(n)
    frac_on = np.zeros(n)
    frac_w_on = np.zeros(n)
    frac_off = np.zeros(n)
    mm = tr.has_mm
    if mm.any():
        st = gating_stats_batch(tr.mm_m[mm], tr.mm_k[mm], tr.mm_n[mm],
                                npu.sa_width)
        frac_on[mm] = st.frac_on
        frac_w_on[mm] = st.frac_w_on
        frac_off[mm] = st.frac_off
        sa_mm = mm & (tr.flops_sa > 0)
        flops_cycles = (tr.mm_m * tr.mm_k).astype(np.float64) * tr.mm_n \
            / (npu.sa_width ** 2)
        dur_cy = np.ones(n)
        dur_cy[mm] = st.duration_cycles
        e = np.minimum(1.0, flops_cycles / np.maximum(1e-9, dur_cy))
        eff[sa_mm] = np.maximum(e[sa_mm], 1e-3)
    t_sa = np.where(tr.flops_sa > 0, tr.flops_sa / (npu.sa_flops * eff), 0.0)
    t_vu = np.where(tr.flops_vu > 0, tr.flops_vu / npu.vu_flops, 0.0)
    t_hbm = np.where(tr.bytes_hbm > 0, tr.bytes_hbm / npu.hbm_bw, 0.0)
    t_ici = np.where(tr.bytes_ici > 0, tr.bytes_ici / npu.ici_bw, 0.0)
    max4 = np.maximum(np.maximum(t_sa, t_vu), np.maximum(t_hbm, t_ici))
    out = {
        "sa": t_sa, "vu": t_vu, "hbm": t_hbm, "ici": t_ici,
        "max4": max4, "dur": np.maximum(max4, 1e-12), "sa_eff": eff,
        "frac_on": frac_on, "frac_w_on": frac_w_on, "frac_off": frac_off,
    }
    tr._derived[id(npu)] = (npu, out)
    return out


def _fine_grained_vu_vec(tm: dict, tr: TraceArrays, npu: NPUSpec,
                         pol: _CompPolicy, p: float, leak_logic: float,
                         knobs: PolicyKnobs) -> dict[str, float]:
    """Vectorized ``fine_grained_vu``: per-burst VU slack inside mixed ops
    (paper Fig 15) — HW detection mostly cannot exploit it, SW setpm can."""
    t_vu = tm["vu"]
    sel = t_vu > 0
    slack = np.where(sel, tm["dur"] - t_vu, 0.0)
    sel = sel & (slack > 0)
    if not sel.any():
        return {"static_j": 0.0, "overhead": 0.0, "wakes": 0.0,
                "setpm": 0.0, "gated_s": 0.0}
    g = npu.gating
    slack = slack[sel]
    n = tr.count[sel]
    active_cy = np.maximum(1.0, npu.cycles(t_vu[sel]))
    n_bursts = np.maximum(1.0, active_cy / g.vu_burst_cycles)
    gap_cy = npu.cycles(slack) / n_bursts
    bet_cy = g.bet["vu"] * knobs.delay_scale
    delay_cy = g.on_off_delay["vu"] * knobs.delay_scale
    window_cy = bet_cy * g.detection_window_frac * knobs.window_scale
    psn = p * slack * n
    if pol.mode == "none":
        return {"static_j": float(psn.sum()), "overhead": 0.0,
                "wakes": 0.0, "setpm": 0.0, "gated_s": 0.0}
    if pol.mode == "ideal":
        return {"static_j": 0.0, "overhead": 0.0, "wakes": 0.0,
                "setpm": 0.0, "gated_s": float((slack * n).sum())}
    if pol.mode == "hw":
        gated = gap_cy > bet_cy
        gated_frac = np.maximum(0.0, (gap_cy - window_cy) / gap_cy)
        e = np.where(gated, psn * ((1 - gated_frac)
                                   + leak_logic * gated_frac), psn)
        gs = np.where(gated, slack * n * gated_frac, 0.0)
        # exposed wake per burst: Base/HW hardware cannot pre-wake
        ov = np.where(gated, n_bursts * delay_cy / npu.freq_hz * n, 0.0)
        wk = np.where(gated, n_bursts * n, 0.0)
        return {"static_j": float(e.sum()), "overhead": float(ov.sum()),
                "wakes": float(wk.sum()), "setpm": 0.0,
                "gated_s": float(gs.sum())}
    # sw
    gated = gap_cy >= np.maximum(bet_cy, 2 * delay_cy)
    trans = np.where(gap_cy > 0, 2 * delay_cy / gap_cy, 0.0)
    e = np.where(gated, psn * (trans + leak_logic * (1 - trans)), psn)
    gs = np.where(gated, slack * n * (1 - trans), 0.0)
    sp = np.where(gated, 2 * n_bursts * n, 0.0)
    wk = np.where(gated, n_bursts * n, 0.0)
    return {"static_j": float(e.sum()), "overhead": 0.0,
            "wakes": float(wk.sum()), "setpm": float(sp.sum()),
            "gated_s": float(gs.sum())}


# --------------------------------------------------------------------------
# evaluation — the result cube (stacked traces × npu × policy × knobs)
# --------------------------------------------------------------------------

@dataclass
class BatchResult:
    """Dense result cube of ``evaluate_batch``: every EnergyReport field
    as a float64 array of shape (workload, npu, policy, knob).

    ``records()`` flattens the cube into the sweep record table
    (workload-major, then NPU, then policy, then knob index — the same
    deterministic ordering the loop sweep emits); ``report()`` rebuilds a
    single ``EnergyReport`` for one cell.
    """

    workloads: tuple[str, ...]
    npus: tuple[NPUSpec, ...]
    policies: tuple[str, ...]
    knob_grid: tuple[PolicyKnobs, ...]
    runtime_s: np.ndarray                    # (W, A, P, K)
    static_j: dict[str, np.ndarray]          # component -> (W, A, P, K)
    dynamic_j: dict[str, np.ndarray]
    wake_events: dict[str, np.ndarray]
    gated_s: dict[str, np.ndarray]
    setpm_by: dict[str, np.ndarray]

    @property
    def shape(self) -> tuple[int, int, int, int]:
        return self.runtime_s.shape

    @property
    def setpm_count(self) -> np.ndarray:
        out = np.zeros(self.shape)
        for c in COMPONENTS:
            out += self.setpm_by[c]
        return out

    def report(self, w: int, a: int, p: int, k: int = 0) -> EnergyReport:
        i = (w, a, p, k)
        return EnergyReport(
            workload=self.workloads[w], policy=self.policies[p],
            npu=self.npus[a].name,
            runtime_s=float(self.runtime_s[i]),
            static_j={c: float(self.static_j[c][i]) for c in COMPONENTS},
            dynamic_j={c: float(self.dynamic_j[c][i]) for c in COMPONENTS},
            setpm_count=sum(float(self.setpm_by[c][i]) for c in COMPONENTS),
            wake_events={c: float(self.wake_events[c][i])
                         for c in COMPONENTS},
            gated_s={c: float(self.gated_s[c][i]) for c in COMPONENTS},
            setpm_by={c: float(self.setpm_by[c][i]) for c in COMPONENTS})

    def records(self) -> list[dict]:
        """Flat sweep record table (same fields, values, and ordering as
        the loop path's per-cell ``_flatten``)."""
        static_tot = np.zeros(self.shape)
        dynamic_tot = np.zeros(self.shape)
        wake_tot = np.zeros(self.shape)
        for c in COMPONENTS:
            static_tot += self.static_j[c]
            dynamic_tot += self.dynamic_j[c]
            wake_tot += self.wake_events[c]
        total = static_tot + dynamic_tot
        setpm = self.setpm_count
        static_frac = static_tot / np.maximum(1e-12, total)
        avg_power = total / np.maximum(1e-12, self.runtime_s)
        freq = np.array([n.freq_hz for n in self.npus])
        setpm_1k = setpm / np.maximum(
            1.0, self.runtime_s * freq[None, :, None, None]) * 1e3

        def col(arr):
            return arr.reshape(-1).tolist()

        cols = [col(self.runtime_s), col(total), col(static_tot),
                col(dynamic_tot), col(static_frac), col(avg_power),
                col(setpm), col(setpm_1k), col(wake_tot)]
        comp_cols = [(f"static_j_{c}", col(self.static_j[c])) for c in
                     COMPONENTS] + [(f"dynamic_j_{c}",
                                     col(self.dynamic_j[c]))
                                    for c in COMPONENTS]
        knobs_meta = [(ki, kn.delay_scale, kn.leak_off_logic,
                       kn.leak_sram_sleep, kn.leak_sram_off, kn.sa_width,
                       kn.window_scale)
                      for ki, kn in enumerate(self.knob_grid)]
        recs = []
        i = 0
        for wname in self.workloads:
            for npu in self.npus:
                for policy in self.policies:
                    for ki, dsc, lol, lss, lso, saw, wsc in knobs_meta:
                        rec = {
                            "workload": wname, "npu": npu.name,
                            "policy": policy, "knob_idx": ki,
                            "delay_scale": dsc, "leak_off_logic": lol,
                            "leak_sram_sleep": lss, "leak_sram_off": lso,
                            "sa_width": saw, "window_scale": wsc,
                            "runtime_s": cols[0][i], "total_j": cols[1][i],
                            "static_total_j": cols[2][i],
                            "dynamic_total_j": cols[3][i],
                            "static_frac": cols[4][i],
                            "avg_power_w": cols[5][i],
                            "setpm_count": cols[6][i],
                            "setpm_per_1k_cycles": cols[7][i],
                            "wake_events": cols[8][i],
                        }
                        for name, cc in comp_cols:
                            rec[name] = cc[i]
                        recs.append(rec)
                        i += 1
        return recs


# --------------------------------------------------------------------------
# evaluation — backend-neutral sweep kernel (numpy or one jitted jax program)
# --------------------------------------------------------------------------

_BK_COMPS = ("sa", "vu", "hbm", "ici")


def _cell_id(c: str, pol: _CompPolicy) -> str:
    """String key for a distinct (component, policy-cell): pytree dict
    keys must sort, so the frozen ``_CompPolicy`` is flattened."""
    return f"{c}|{pol.mode}|{pol.delay_key}|{int(pol.spatial_sa)}"


def _distinct_cells(policies) -> dict[str, tuple[str, _CompPolicy]]:
    out: dict[str, tuple[str, _CompPolicy]] = {}
    for p in policies:
        cp = _component_policies(p)
        for c in _BK_COMPS:
            out.setdefault(_cell_id(c, cp[c]), (c, cp[c]))
    return out


def _sram_states(policies) -> tuple[str, ...]:
    return tuple(dict.fromkeys(
        _component_policies(p)["sram"].sram_state for p in policies))


def _sweep_kernel(data, knobs, policies, bk, wl_axis=None, knob_axis=None):
    """The whole sweep — service times, SA occupancy, gap merges, and
    the policy/knob assembly — as one pure, backend-neutral program
    over fixed-shape arrays.

    ``data`` carries the *raw* per-op columns (FLOPs, bytes, matmul
    dims), the host-built fixed-shape gap index (``backend.gap_index``
    — chunk ownership replaces the data-dependent ``reduceat`` of
    ``segmented_gaps``), and per-NPU scalars as 0-d arrays so one
    compiled program serves every NPU generation. Unlike the PR-4
    kernel, the per-op service times and the SA PE-occupancy closed
    form (``bk.sa_occupancy``) are computed *inside* the traced
    program: the SA width ``saw`` enters as a traced scalar, which is
    what turns ``sa_width`` into a real knob axis (ISSUE 5). Distinct
    ``_CompPolicy`` cells are computed once and shared across policies
    (memoized at trace time).

    The knob axis is factored: the O(n_ops)-sized work — occupancy,
    service times, gap merges, masked threshold merges — depends only
    on ``(sa_width, delay_scale, window_scale)``, and every leakage
    knob enters *linearly after* the segmented reductions. So the
    heavy passes run through ``bk.vmap_knobs`` over the **unique**
    (saw, delay-scale, window-scale) triples
    (``knobs["pair_saw_idx"]/["pair_dscale"]/["pair_wscale"]``) and
    the full knob grid is assembled from those primitives with
    O(W × K) linear algebra. A crossed width × threshold × leakage
    grid therefore costs ``len(unique triples)`` heavy passes, not
    ``K``.

    Under ``shard_map`` (the multi-device path) the op axis may be
    sharded over the ``wl_axis`` mesh axis — every op-axis segment sum
    is then completed with a ``psum`` — and the pair + knob axes over
    ``knob_axis``: each device runs the heavy passes for its local
    pairs, ``all_gather``s the (small) per-segment primitives, and
    assembles only its local knob slice.

    Returns a dict of (K, W) arrays: per-cell quantities (``cells``),
    SRAM static per state (``sram``), and the per-knob context
    (``D_seg``, ``dyn``, ``sram_GU``, ``sram_dyn``) the host assembly
    broadcasts from. ``_backend_kernel`` and ``_shard_kernel`` return
    them ``_pack``-ed into one array, so the host pulls it once.
    """
    xp = bk.xp
    op = data["op"]
    offsets = data["offsets"]
    scal = data["scal"]
    w = offsets.shape[0] - 1
    seg = op["seg_ids"]
    cnt = op["cnt"]

    def opsum(v, ids, num):
        """Segment sum over the (possibly device-sharded) op axis."""
        s = bk.segment_sum(v, ids, num)
        return bk.psum(s, wl_axis) if wl_axis else s

    def segsum(v):
        return opsum(v, seg, w)

    cells = _distinct_cells(policies)
    states = _sram_states(policies)
    used = op["sram_used"]

    def per_saw(kd):
        """Everything that depends on the SA width alone: traced
        service times + PE occupancy (``trace_times``, bitwise-equal
        float64 ops), the per-op gap/slack structures, and the
        per-segment base sums the leakage knobs assemble from
        linearly. Vmapped over the UNIQUE widths only — a pure delay/
        leakage grid computes all of this exactly once."""
        saw = kd["saw"]
        has_mm = op["has_mm"]
        occ = bk.sa_occupancy(op["mm_m"], op["mm_k"], op["mm_n"], saw)
        frac_on = xp.where(has_mm, occ["frac_on"], 0.0)
        frac_w_on = xp.where(has_mm, occ["frac_w_on"], 0.0)
        frac_off = xp.where(has_mm, occ["frac_off"], 0.0)
        sa_flops = saw * saw * 2.0 * scal["n_sa"] * scal["freq"]
        flops_cycles = op["mm_m"] * op["mm_k"] * op["mm_n"] / (saw * saw)
        dur_cy = xp.where(has_mm, occ["duration_cycles"], 1.0)
        e = xp.minimum(1.0, flops_cycles / xp.maximum(1e-9, dur_cy))
        eff = xp.where(has_mm & (op["flops_sa"] > 0),
                       xp.maximum(e, 1e-3), 1.0)
        t = {"sa": xp.where(op["flops_sa"] > 0,
                            op["flops_sa"] / (sa_flops * eff), 0.0),
             "vu": xp.where(op["flops_vu"] > 0,
                            op["flops_vu"] / scal["vu_flops"], 0.0),
             "hbm": xp.where(op["bytes_hbm"] > 0,
                             op["bytes_hbm"] / scal["hbm_bw"], 0.0),
             "ici": xp.where(op["bytes_ici"] > 0,
                             op["bytes_ici"] / scal["ici_bw"], 0.0)}
        max4 = xp.maximum(xp.maximum(t["sa"], t["vu"]),
                          xp.maximum(t["hbm"], t["ici"]))
        dur = xp.maximum(max4, 1e-12)
        durn = dur * cnt

        base = {"D_seg": segsum(durn)}
        comp: dict[str, dict] = {}
        for c in _BK_COMPS:
            a = t[c]
            active = a > 0
            gseg = data["gap_seg"][c]
            gap_vals = opsum(xp.where(active, 0.0, durn),
                             op[f"chunk_{c}"], gseg.shape[0])
            slack = xp.where(active, dur - a, 0.0)
            comp[c] = {"gap_vals": gap_vals, "slack": slack,
                       "scnt": slack * cnt}
            # gap_vals is already globally summed (and so replicated
            # across wl shards): its per-segment merges need no psum
            base[f"S_gap_{c}"] = bk.segment_sum(gap_vals, gseg, w)
            base[f"S_slk_{c}"] = segsum(slack * cnt)
            base[f"AN_{c}"] = segsum(a * cnt)
            acnt = a * cnt
            if c == "sa":
                sa_acnt = acnt
        for c in ("vu", "hbm", "ici"):
            base[f"dyn_{c}"] = scal[f"dyn_w_{c}"] * base[f"AN_{c}"]
        base["dyn_sa"] = scal["dyn_w_sa"] * segsum(
            op["flops_sa"] / sa_flops * cnt)
        # SA spatial occupancy is linear in leak_logic with
        # width-dependent segment sums: occ = A + leak_logic * B per op
        base["occ_ideal_AN"] = segsum(
            xp.where(has_mm, frac_on, 1.0) * sa_acnt)
        base["sa_occ_an_a"] = segsum(xp.where(
            has_mm, frac_on + scal["leak_pe_weight_on"] * frac_w_on,
            1.0) * sa_acnt)
        base["sa_occ_an_b"] = segsum(
            xp.where(has_mm, frac_off, 0.0) * sa_acnt)
        # VU fine-grained burst structure (paper Fig 15)
        vu = comp["vu"]
        sel = (t["vu"] > 0) & (vu["slack"] > 0)
        active_cy = xp.maximum(1.0, scal["freq"] * t["vu"])
        n_bursts = xp.maximum(1.0, active_cy / scal["vu_burst_cycles"])
        gap_raw = scal["freq"] * vu["slack"] / n_bursts
        psn = scal["static_w_vu"] * vu["slack"] * cnt
        vu.update(sel=sel, nbn=n_bursts * cnt,
                  gap_cy=xp.where(sel, gap_raw, 0.0),
                  inv_gap=xp.where(sel, 1.0 / xp.where(sel, gap_raw, 1.0),
                                   0.0),
                  psn=psn)
        base["PSN_seg"] = segsum(psn)
        # SRAM capacity model (the demand pattern is width-independent;
        # the setpm boundary count is knob-free and counted host-side)
        base["sram_U"] = segsum(durn * used)
        base["sram_GU"] = segsum(durn * (1.0 - used))
        base["sram_dyn"] = scal["dyn_w_sram"] * 0.5 * segsum(max4 * cnt)
        return {"base": base, "comp": comp}

    sb = bk.vmap_knobs(per_saw, {"saw": knobs["saw_unique"]})
    if knob_axis:
        # the unique-width axis is device-sharded too: gather the
        # per-saw structures (small: (S, n) per-op columns and (S, W)
        # sums) so every device can run its local pairs and knobs
        sb = bk.all_gather(sb, knob_axis)

    def per_pair(kd):
        """The masked threshold merges for ONE (saw, delay-scale) pair;
        the width-dependent structures are gathered from the stacked
        per-saw pass by index."""
        si, d, ws = kd["si"], kd["dscale"], kd["wscale"]
        comp = {c: {q: arr[si] for q, arr in cd.items()}
                for c, cd in sb["comp"].items()}
        prims = {}
        for cid, (c, pol) in cells.items():
            if pol.mode not in ("hw", "sw"):
                continue  # none/ideal need no masked primitives
            cc = comp[c]
            bet = scal[f"bet_{pol.delay_key}"] * d / scal["freq"]
            delay = scal[f"delay_{pol.delay_key}"] * d / scal["freq"]
            window = bet * scal["window_frac"] * ws
            gv = cc["gap_vals"]
            if pol.mode == "hw":
                gmask = gv > window
            else:
                gmask = (gv >= xp.maximum(bet, 2.0 * delay)) & (gv > 0)
            gseg = data["gap_seg"][c]
            o = {"GM": bk.segment_sum(xp.where(gmask, gv, 0.0), gseg, w),
                 "GC": bk.segment_sum(xp.where(gmask, 1.0, 0.0),
                                      gseg, w)}
            if c == "vu":
                # fine-grained burst slack: static energy is
                # VA + leak * VB; VG is gated seconds, NB burst count
                bet_cy = scal["bet_vu"] * d
                delay_cy = scal["delay_vu"] * d
                gap_cy = cc["gap_cy"]
                psn_ = cc["psn"]
                if pol.mode == "hw":
                    window_cy = bet_cy * scal["window_frac"] * ws
                    gm = gap_cy > bet_cy
                    gf = xp.maximum(0.0, 1.0 - window_cy * cc["inv_gap"])
                    o["VA"] = segsum(xp.where(gm, psn_ * (1.0 - gf), psn_))
                    o["VB"] = segsum(xp.where(gm, psn_ * gf, 0.0))
                    o["VG"] = segsum(xp.where(gm, cc["scnt"] * gf, 0.0))
                else:
                    gm = cc["sel"] & (
                        gap_cy >= xp.maximum(bet_cy, 2.0 * delay_cy))
                    trans = 2.0 * delay_cy * cc["inv_gap"]
                    o["VA"] = segsum(xp.where(gm, psn_ * trans, psn_))
                    o["VB"] = segsum(
                        xp.where(gm, psn_ * (1.0 - trans), 0.0))
                    o["VG"] = segsum(
                        xp.where(gm, cc["scnt"] * (1.0 - trans), 0.0))
                o["NB"] = segsum(xp.where(gm, cc["nbn"], 0.0))
            else:
                slack = cc["slack"]
                if pol.mode == "hw":
                    smask = slack > window
                else:
                    smask = (slack >= xp.maximum(bet, 2.0 * delay)) \
                        & (slack > 0)
                o["SM"] = segsum(xp.where(smask, cc["scnt"], 0.0))
                o["SC"] = segsum(xp.where(smask, cnt, 0.0))
            prims[cid] = o
        return prims

    all_prims = bk.vmap_knobs(per_pair, {"si": knobs["pair_saw_idx"],
                                         "dscale": knobs["pair_dscale"],
                                         "wscale": knobs["pair_wscale"]})
    if knob_axis:
        # pairs are device-sharded: gather the (U, W)-sized primitives
        # so every device can assemble its local knob slice
        all_prims = bk.all_gather(all_prims, knob_axis)
    inv = knobs["pair_inv"]
    # per-knob base sums: (K, W) via the knob -> unique-width index
    base = {k: v[knobs["saw_inv"]] for k, v in sb["base"].items()}

    # ---- full-knob assembly: O(W × K) linear algebra on the primitives
    k_full = knobs["dscale"].shape[0]
    dscale = knobs["dscale"][:, None]          # (K, 1)
    wscale = knobs["wscale"][:, None]          # (K, 1)
    leak_logic = knobs["leak_logic"][:, None]

    def cell(c, pol):
        """(K, W) closed-form assembly of one component-policy cell."""
        p = scal[f"static_w_{c}"]
        leak = leak_logic
        if c == "hbm":
            # HBM auto-refresh floor (paper §6.5)
            leak = xp.maximum(leak, scal["leak_hbm_refresh"])
        acc = {q: xp.zeros((k_full, w)) for q in
               ("static", "overhead", "wakes", "setpm", "gated")}
        s_gap = base[f"S_gap_{c}"]
        gating = pol.mode in ("hw", "sw")
        if gating:
            pr = {q: a[inv]
                  for q, a in all_prims[_cell_id(c, pol)].items()}
            bet = scal[f"bet_{pol.delay_key}"] * dscale / scal["freq"]
            delay = scal[f"delay_{pol.delay_key}"] * dscale / scal["freq"]
            window = bet * scal["window_frac"] * wscale

        # --- merged cross-op idle gaps (each closed once) ---
        if pol.mode == "none":
            acc["static"] = acc["static"] + p * s_gap
        elif pol.mode == "ideal":
            acc["gated"] = acc["gated"] + s_gap
        else:
            gm, gc = pr["GM"], pr["GC"]
            if pol.mode == "hw":
                acc["static"] = acc["static"] + p * (s_gap - gm) \
                    + (p * window) * gc + (leak * p) * (gm - window * gc) \
                    + (p * delay) * gc
                acc["overhead"] = acc["overhead"] + delay * gc
                acc["gated"] = acc["gated"] + gm - window * gc
            else:
                acc["static"] = acc["static"] + p * (s_gap - gm) \
                    + (leak * p) * (gm - 2.0 * delay * gc) \
                    + (p * 2.0 * delay) * gc
                acc["setpm"] = acc["setpm"] + 2.0 * gc
                acc["gated"] = acc["gated"] + gm - 2.0 * delay * gc
            acc["wakes"] = acc["wakes"] + gc

        # --- active-portion static (SA: PE-occupancy weighted) ---
        if c == "sa" and pol.spatial_sa:
            if pol.mode == "ideal":
                acc["static"] = acc["static"] + p * base["occ_ideal_AN"]
            else:
                acc["static"] = acc["static"] + p * (
                    base["sa_occ_an_a"] + leak_logic * base["sa_occ_an_b"])
        else:
            acc["static"] = acc["static"] + p * base[f"AN_{c}"]

        # --- within-op slack (per executed instance) ---
        if c == "vu":
            if pol.mode == "none":
                acc["static"] = acc["static"] + base["PSN_seg"]
            elif pol.mode == "ideal":
                acc["gated"] = acc["gated"] + base["S_slk_vu"]
            else:
                acc["static"] = acc["static"] + pr["VA"] + leak * pr["VB"]
                acc["gated"] = acc["gated"] + pr["VG"]
                nb = pr["NB"]
                if pol.mode == "hw":
                    # exposed wake per burst: HW cannot pre-wake
                    acc["overhead"] = acc["overhead"] \
                        + (scal["delay_vu"] * dscale / scal["freq"]) * nb
                else:
                    acc["setpm"] = acc["setpm"] + 2.0 * nb
                acc["wakes"] = acc["wakes"] + nb
        else:
            s_slk = base[f"S_slk_{c}"]
            if pol.mode == "none":
                acc["static"] = acc["static"] + p * s_slk
            elif pol.mode == "ideal":
                acc["gated"] = acc["gated"] + s_slk
            else:
                sm, cm = pr["SM"], pr["SC"]
                if pol.mode == "hw":
                    lo, hi = window, delay
                    acc["static"] = acc["static"] + p * (s_slk - sm) \
                        + (p * lo) * cm + (leak * p) * (sm - lo * cm) \
                        + (p * hi) * cm
                    acc["overhead"] = acc["overhead"] + hi * cm
                else:
                    lo = 2.0 * delay
                    acc["static"] = acc["static"] + p * (s_slk - sm) \
                        + (leak * p) * (sm - lo * cm) + (p * lo) * cm
                    acc["setpm"] = acc["setpm"] + 2.0 * cm
                acc["wakes"] = acc["wakes"] + cm
                acc["gated"] = acc["gated"] + sm - lo * cm

        if c in ("hbm", "ici"):
            # wake overlapped with the long DMA issue latency half the time
            acc["overhead"] = acc["overhead"] * 0.5
        return acc

    out_cells = {cid: cell(c, pol) for cid, (c, pol) in cells.items()}
    out_sram = {}
    for state in states:
        lk = {"on": xp.ones((k_full, 1)),
              "sleep": knobs["leak_sleep"][:, None],
              "off": knobs["leak_off"][:, None]}.get(
                  state, xp.zeros((k_full, 1)))
        out_sram[state] = scal["static_w_sram"] * (
            base["sram_U"] + lk * base["sram_GU"])
    return {"cells": out_cells, "sram": out_sram,
            "D_seg": base["D_seg"],
            "dyn": {c: base[f"dyn_{c}"] for c in _BK_COMPS},
            "sram_GU": base["sram_GU"], "sram_dyn": base["sram_dyn"]}


_CELL_QS = ("static", "wakes", "setpm", "gated", "overhead")

# the order in which the sweep program packs its outputs, per policies
# tuple: fixed at trace time, rebuilt by the host from the same policies
_LAYOUTS: dict[tuple, tuple] = {}


def _out_layout(policies) -> tuple[tuple[str, ...], ...]:
    """Key paths of ``_sweep_kernel``'s output leaves, in the order
    ``_pack`` stacks them and ``_unpack`` reads them back."""
    hit = _LAYOUTS.get(policies)
    if hit is None:
        hit = (tuple(("cells", cid, q) for cid in _distinct_cells(policies)
                     for q in _CELL_QS)
               + tuple(("sram", s) for s in _sram_states(policies))
               + (("D_seg",),) + tuple(("dyn", c) for c in _BK_COMPS)
               + (("sram_GU",), ("sram_dyn",)))
        _LAYOUTS[policies] = hit
    return hit


def _pack(out: dict, policies, xp):
    """Every (K, W) output leaf stacked into one (n_out, K, W) slab in
    ``_out_layout`` order, so one transfer harvests an NPU. Stacking
    copies float64 exactly."""
    def leaf(path):
        v = out
        for key in path:
            v = v[key]
        return v
    return xp.stack([leaf(path) for path in _out_layout(policies)])


def _unpack(slab: np.ndarray, policies) -> dict:
    """``_pack`` undone on the host: the kernel's output dict, each leaf
    a (W, K) view of the (n_out, K, W) ``slab``."""
    out: dict = {}
    for path, arr in zip(_out_layout(policies), slab):
        d = out
        for key in path[:-1]:
            d = d.setdefault(key, {})
        d[path[-1]] = arr.T
    return out


# jitted sweep kernels cached per backend: the jax program compiles
# once per (stack shape, knob count, policies) and is reused across NPU
# generations and repeated sweeps
_KERNELS: dict[str, object] = {}


def _backend_kernel(bk):
    """The (possibly jitted) single-device sweep kernel for one
    backend, its outputs packed by ``_pack``. ``layouts`` (static) is
    the ``(data, knobs)`` pair of ``slab_layout``s where the inputs come
    as ``put_slabs`` slabs, rebuilt here by ``unpack_slabs``; ``None``
    for either where it comes as a dict of per-leaf arrays (the GSPMD
    mesh path's op columns)."""
    fn = _KERNELS.get(bk.name)
    if fn is None:
        def kern(data, knobs, policies, layouts=(None, None)):
            data, knobs = (x if lay is None else unpack_slabs(x, lay)
                           for x, lay in zip((data, knobs), layouts))
            return _pack(_sweep_kernel(data, knobs, policies, bk),
                         policies, bk.xp)
        fn = bk.jit(kern, static_argnames=("policies", "layouts"))
        _KERNELS[bk.name] = fn
    return fn


# shard_map sweep programs, keyed by (backend, mesh identity, policies,
# axes); the value keeps a strong ref to the mesh so its id cannot be
# reused while the entry lives
_SHARD_KERNELS: dict[tuple, tuple] = {}


def _shard_kernel(bk, mesh, policies, wl_axis, knob_axis):
    """One SPMD sweep program over ``mesh``: op columns sharded over
    ``wl_axis`` (completed by in-kernel psums), unique (saw, delay)
    pairs and the knob grid sharded over ``knob_axis``; everything
    else replicated. Inputs must be padded to the axis sizes
    (``_sharded_backend_data`` / ``_knob_columns(pad_to=...)``)."""
    key = (bk.name, id(mesh), policies, wl_axis, knob_axis)
    hit = _SHARD_KERNELS.get(key)
    if hit is not None and hit[0] is mesh:
        return hit[1]
    pspec = bk.pspec
    data_spec = {"op": pspec(wl_axis) if wl_axis else pspec(),
                 "gap_seg": pspec(), "offsets": pspec(), "scal": pspec()}
    # every knob-array axis (knobs, pairs, unique widths) is sharded
    # over the knob mesh axis; the kernel gathers what it must share
    knob_spec = pspec(knob_axis)

    def body(data, knobs):
        return _pack(_sweep_kernel(data, knobs, policies, bk,
                                   wl_axis=wl_axis, knob_axis=knob_axis),
                     policies, bk.xp)

    # the packed slab's knob axis (axis 1) is the sharded one
    fn = bk.shard_map_kernel(body, mesh,
                             in_specs=(data_spec, knob_spec),
                             out_specs=pspec(None, knob_axis))
    _SHARD_KERNELS[key] = (mesh, fn)
    return fn


def _gap_indices(st: StackedTrace) -> dict[str, tuple]:
    """Fixed-shape gap-chunk indices per component — depend only on the
    activity pattern and segmentation, so one set per stack serves every
    NPU generation (cached on the stack)."""
    hit = st._derived.get("gap_index")
    if hit is None:
        cols = {"sa": st.flops_sa, "vu": st.flops_vu,
                "hbm": st.bytes_hbm, "ici": st.bytes_ici}
        hit = {c: gap_index(cols[c] > 0, st.offsets) for c in _BK_COMPS}
        st._derived["gap_index"] = hit
    return hit


def _mm_columns(st: StackedTrace) -> tuple[np.ndarray, ...]:
    """Concatenated float64 matmul-dim columns (NPU-independent; the
    kernel consumes them as exact-integer floats so the traced
    occupancy math stays bitwise equal to the int64 host path)."""
    hit = st._derived.get("mm_columns")
    if hit is None:
        def cat(attr):
            if not st.traces:
                return np.zeros(0)
            return np.concatenate(
                [getattr(tr, attr) for tr in st.traces]).astype(np.float64)
        hit = (cat("mm_m"), cat("mm_k"), cat("mm_n"))
        st._derived["mm_columns"] = hit
    return hit


def _host_columns(st: StackedTrace, npu: NPUSpec) -> tuple[dict,
                                                           np.ndarray]:
    """Host-side kernel input pytree for one (stack, NPU) plus the
    knob-free SRAM setpm boundary counts (W,).

    Only *raw* trace columns and per-NPU scalars — no service times, no
    occupancy: those are traced inside the kernel now, which is what
    lets ``sa_width`` ride the knob axis. Per-NPU scalars are values
    of the float64 slab (``_backend_data``), or 0-d arrays on a mesh
    path, so swapping generations never retraces the compiled program.
    Cached on the stack (spec-identity keyed)."""
    key = ("host_columns", id(npu))
    hit = st._derived.get(key)
    if hit is not None and hit[0] is npu:
        return hit[1], hit[2]
    gidx = _gap_indices(st)
    mm_m, mm_k, mm_n = _mm_columns(st)
    pm = PowerModel(npu)
    g = npu.gating
    used = np.minimum(1.0, st.sram_demand / npu.sram_bytes)
    op = {
        "seg_ids": st.seg_ids, "cnt": st.count,
        "flops_sa": st.flops_sa, "flops_vu": st.flops_vu,
        "bytes_hbm": st.bytes_hbm, "bytes_ici": st.bytes_ici,
        "has_mm": st.has_mm, "mm_m": mm_m, "mm_k": mm_k, "mm_n": mm_n,
        "sram_used": used,
    }
    for c in _BK_COMPS:
        op[f"chunk_{c}"] = gidx[c][0]
    scal = {"freq": npu.freq_hz, "n_sa": float(npu.n_sa),
            "vu_flops": npu.vu_flops, "hbm_bw": npu.hbm_bw,
            "ici_bw": npu.ici_bw,
            "window_frac": g.detection_window_frac,
            "leak_hbm_refresh": g.leak_hbm_refresh,
            "leak_pe_weight_on": g.leak_pe_weight_on,
            "vu_burst_cycles": float(g.vu_burst_cycles)}
    for c, v in pm.static_w.items():
        scal[f"static_w_{c}"] = v
    for c, v in pm.dyn_max_w.items():
        scal[f"dyn_w_{c}"] = v
    for k, v in g.bet.items():
        scal[f"bet_{k}"] = float(v)
    for k, v in g.on_off_delay.items():
        scal[f"delay_{k}"] = float(v)
    # SRAM setpm: one range-setpm pair per demand-CHANGE boundary
    # (knob- and width-free → counted here, off the traced path)
    w = st.n_segments
    changes = np.zeros(w)
    first = np.zeros(w)
    if st.n_ops:
        b = (used[1:] != used[:-1]) & (st.seg_ids[1:] == st.seg_ids[:-1])
        changes = np.bincount(st.seg_ids[1:][b],
                              minlength=w).astype(np.float64)
        starts = st.offsets[:-1]
        nonempty = st.offsets[1:] > starts
        first[nonempty] = used[starts[nonempty]] < 1.0
    sram_setpm = 2.0 * (changes + first)
    host = {"op": op, "gap_seg": {c: gidx[c][1] for c in _BK_COMPS},
            "offsets": st.offsets, "scal": scal}
    st._derived[key] = (npu, host, sram_setpm)
    return host, sram_setpm


def _put_tree(tree, bk):
    """One ``regate.put`` of a host pytree, leaf by leaf: the mesh
    paths, whose ``in_specs`` and shardings place each leaf."""
    def put(t):
        if isinstance(t, dict):
            return {k: put(v) for k, v in t.items()}
        return bk.asarray(t)

    with bk.span("regate.put", lambda: transfer_counts(tree)):
        return put(tree)


def _backend_data(st: StackedTrace, npu: NPUSpec, bk) \
        -> tuple[tuple, np.ndarray]:
    """``_host_columns`` transferred to the backend once, as one float64
    and one int64 slab (``put_slabs``: ``(layout, slabs)``), and cached
    on the stack (spec-identity keyed, same convention as
    ``trace_times``)."""
    key = ("backend_data", bk.name, id(npu))
    hit = st._derived.get(key)
    if hit is not None and hit[0] is npu:
        return hit[1], hit[2]
    with bk.span("regate.host_columns"):
        host, sram_setpm = _host_columns(st, npu)
    data = put_slabs(host, bk)
    st._derived[key] = (npu, data, sram_setpm)
    return data, sram_setpm


def _sharded_backend_data(st: StackedTrace, npu: NPUSpec, bk,
                          wl_size: int) -> tuple[dict, np.ndarray]:
    """``_backend_data``'s columns put leaf by leaf (``_put_tree``), for
    the mesh paths that shard them, with the op axis padded to a
    multiple of the ``wl`` mesh-axis size so it splits evenly.

    Padded ops are inert by construction: count 0, no FLOPs/bytes (so
    never active, zero duration), sentinel 1×1×1 matmul dims with
    ``has_mm`` False, and segment/chunk ids pinned to the LAST id —
    keeping the ids sorted (the jax segment sums rely on it) while the
    zero weights contribute nothing to any segment."""
    key = ("backend_data_sharded", bk.name, id(npu), wl_size)
    hit = st._derived.get(key)
    if hit is not None and hit[0] is npu:
        return hit[1], hit[2]
    with bk.span("regate.host_columns"):
        host, sram_setpm = _host_columns(st, npu)
    op = dict(host["op"])
    n = len(op["seg_ids"])
    pad = (-n) % wl_size
    if pad:
        fill = {"seg_ids": st.n_segments - 1, "has_mm": False,
                "mm_m": 1.0, "mm_k": 1.0, "mm_n": 1.0}
        for k, a in op.items():
            if k.startswith("chunk_"):
                v = max(len(host["gap_seg"][k[6:]]) - 1, 0)
            else:
                v = fill.get(k, 0.0)
            op[k] = np.concatenate([a, np.full(pad, v, a.dtype)])
    data = _put_tree({**host, "op": op}, bk)
    st._derived[key] = (npu, data, sram_setpm)
    return data, sram_setpm


def knob_pairs(knob_grid) -> "tuple[list[tuple], np.ndarray]":
    """Unique (sa_width, delay_scale, window_scale) triples of a knob
    grid and the knob -> triple inverse map — the axes the executors
    actually see (leak knobs are post-hoc linear and never change
    machine behavior). The host-side twin of ``_knob_columns``'s
    unique-pair dedup, shared with the batched program plane
    (``repro.core.program_plane``): knob points differing only in leak
    ratios map onto one executor row."""
    trips: list[tuple] = []
    index: dict[tuple, int] = {}
    inv = np.empty(len(knob_grid), np.int64)
    for i, k in enumerate(knob_grid):
        key = (k.sa_width, float(k.delay_scale), float(k.window_scale))
        if key not in index:
            index[key] = len(trips)
            trips.append(key)
        inv[i] = index[key]
    return trips, inv


def _knob_columns(knob_grid, npu: NPUSpec, pad_to: int = 0) -> dict:
    """Knob-grid host arrays for the kernel: the full per-knob columns
    plus the unique (sa_width, delay_scale, window_scale) triples the
    heavy passes vmap over, with the inverse index mapping them back
    onto the grid.
    ``pad_to`` pads the knob and pair axes to a multiple (repeating
    entry 0) so ``shard_map`` can split them evenly — the host slices
    the padded tail off the outputs."""
    g = npu.gating
    ds = np.array([k.delay_scale for k in knob_grid], np.float64)
    ws = np.array([k.window_scale for k in knob_grid], np.float64)
    saw = np.array([float(k.sa_width) if k.sa_width is not None
                    else float(npu.sa_width) for k in knob_grid])
    leak_logic = np.array(
        [k.leak_off_logic if k.leak_off_logic is not None
         else g.leak_off_logic for k in knob_grid], np.float64)
    leak_sleep = np.array(
        [k.leak_sram_sleep if k.leak_sram_sleep is not None
         else g.leak_sram_sleep for k in knob_grid], np.float64)
    leak_off = np.array(
        [k.leak_sram_off if k.leak_sram_off is not None
         else g.leak_sram_off for k in knob_grid], np.float64)
    saw_unique, saw_inv = np.unique(saw, return_inverse=True)
    saw_inv = saw_inv.reshape(-1).astype(np.int64)
    pairs = np.stack([saw, ds, ws], axis=1)
    uniq, inv = np.unique(pairs, axis=0, return_inverse=True)
    inv = inv.reshape(-1).astype(np.int64)
    pair_saw_idx = np.searchsorted(saw_unique, uniq[:, 0]).astype(np.int64)
    pair_ds = uniq[:, 1].copy()
    pair_ws = uniq[:, 2].copy()

    def padded(a, m):
        p = (-len(a)) % m
        return a if p == 0 else np.concatenate([a, np.repeat(a[:1], p)])

    if pad_to:
        ds, ws, leak_logic, leak_sleep, leak_off, inv, saw_inv = (
            padded(a, pad_to)
            for a in (ds, ws, leak_logic, leak_sleep, leak_off, inv,
                      saw_inv))
        # pair and unique-width axes are device-sharded as well; pads
        # repeat entry 0 / width 0 (inert duplicates — the inverse
        # indices never point at them, padding sits at the END)
        pair_saw_idx, pair_ds, pair_ws, saw_unique = (
            padded(a, pad_to)
            for a in (pair_saw_idx, pair_ds, pair_ws, saw_unique))
    host = {
        "dscale": ds,
        "wscale": ws,
        "leak_logic": leak_logic,
        "leak_sleep": leak_sleep,
        "leak_off": leak_off,
        # the width-dependent base pass runs once per distinct width
        # (replicated under shard_map); the heavy masked merges once per
        # distinct (width, delay) pair; the inverse indices map both
        # back onto the full grid
        "saw_unique": saw_unique,
        "saw_inv": saw_inv,
        "pair_saw_idx": pair_saw_idx,
        "pair_dscale": pair_ds,
        "pair_wscale": pair_ws,
        "pair_inv": inv,
    }
    return host


def _knob_arrays(knob_grid, npu: NPUSpec, bk) -> tuple[tuple, dict]:
    """``_knob_columns`` put as one float64 and one int64 slab
    (``put_slabs``: ``(layout, slabs)``): every path but ``shard_map``'s,
    whose ``in_specs`` shard each knob leaf (``_put_tree``)."""
    return put_slabs(_knob_columns(knob_grid, npu), bk)


def _evaluate_batch_backend(workloads, npu_specs, policies, knob_grid,
                            bk, mesh=None) -> BatchResult:
    """``evaluate_batch`` through the backend-neutral kernel.

    On the jax backend the whole per-NPU evaluation is one jitted
    program. A ``parallel.jax_compat`` mesh selects the multi-device
    path: a mesh with a ``"knob"`` axis (optionally crossed with
    ``"wl"``) runs the explicit ``shard_map`` program — pairs + knobs
    sharded over ``"knob"``, op columns over ``"wl"`` — while a pure
    ``("wl",)`` mesh keeps the GSPMD path (sharded ``device_put`` into
    the ordinary jitted kernel).

    Every NPU's kernel is launched, and its slab's copy to the host
    started, before the first is harvested, so the host's pulls and
    assembly of one NPU run while the device computes the next.
    """
    st = stack_traces(workloads)
    policies = tuple(policies)
    w, a_n, p_n, k_n = st.n_segments, len(npu_specs), len(policies), \
        len(knob_grid)
    shape = (w, a_n, p_n, k_n)
    runtime = np.zeros(shape)
    static_j = {c: np.zeros(shape) for c in COMPONENTS}
    dynamic_j = {c: np.zeros(shape) for c in COMPONENTS}
    wake_events = {c: np.zeros(shape) for c in COMPONENTS}
    gated_s = {c: np.zeros(shape) for c in COMPONENTS}
    setpm_by = {c: np.zeros(shape) for c in COMPONENTS}
    result = BatchResult(
        workloads=tuple(st.names), npus=tuple(npu_specs),
        policies=policies, knob_grid=tuple(knob_grid),
        runtime_s=runtime, static_j=static_j, dynamic_j=dynamic_j,
        wake_events=wake_events, gated_s=gated_s, setpm_by=setpm_by)
    if w == 0:
        return result
    wl_axis = knob_axis = None
    wl_size = knob_size = 1
    if mesh is not None:
        sizes = bk.mesh_axis_sizes(mesh)
        wl_size = sizes.get("wl", 1)
        if "knob" in sizes:
            knob_axis, knob_size = "knob", sizes["knob"]
            if "wl" in sizes:
                wl_axis = "wl"
    with bk.compute_scope():
        # Launch every NPU's kernel before the first harvest: while the
        # host pulls and assembles one NPU, the device runs the next.
        launched = []
        for npu in npu_specs:
            if knob_axis is not None:
                data, sram_setpm = _sharded_backend_data(st, npu, bk,
                                                         wl_size)
                knobs = _put_tree(_knob_columns(knob_grid, npu,
                                                pad_to=knob_size), bk)
                kern = _shard_kernel(bk, mesh, policies, wl_axis,
                                     knob_axis)
                args = (data, knobs)
            else:
                if mesh is None:
                    # one slab per dtype: at most 2 puts, cached
                    (data_layout, data), sram_setpm = _backend_data(
                        st, npu, bk)
                else:
                    # the op axis must divide the "wl" axis: pad it
                    # with inert ops, exactly as the shard_map path does,
                    # and shard it leaf by leaf
                    data, sram_setpm = _sharded_backend_data(
                        st, npu, bk, wl_size)
                    data, data_layout = bk.shard_data(data, mesh), None
                knob_layout, knobs = _knob_arrays(knob_grid, npu, bk)
                kern = _backend_kernel(bk)
                args = (data, knobs, policies, (data_layout, knob_layout))
            # the launch alone: the pull waits for the kernel
            with bk.span("regate.sweep_kernel"):
                slab = kern(*args)
                bk.copy_to_host_async(slab)
            launched.append((slab, sram_setpm))

        for ai, (npu, (slab, sram_setpm)) in enumerate(
                zip(npu_specs, launched)):
            # one pull per NPU: (n_out, K_pad, W), shard padding dropped;
            # it waits for the kernel if that has not finished. Beside
            # the transfer, the span counts the work of the call it
            # harvests: the stack's op rows (before any mesh padding),
            # those with matmul dims, and the knob points; and the
            # kernel calls launched and not yet harvested, this one
            # included.
            with bk.span("regate.harvest", lambda: dict(
                    transfer_counts(slab), ops=st.n_ops,
                    mm_ops=int(st.has_mm.sum()), knobs=k_n,
                    in_flight=len(launched) - ai)):
                vm = _unpack(bk.to_numpy(slab)[:, :k_n], policies)
            cells, sram_static, dyn = vm["cells"], vm["sram"], vm["dyn"]
            d_seg, sram_gu, sram_dyn = \
                vm["D_seg"], vm["sram_GU"], vm["sram_dyn"]
            with bk.span("regate.assemble"):
                pm = PowerModel(npu)
                for pi, policy in enumerate(policies):
                    cp = _component_policies(policy)
                    ov_total = np.zeros((w, k_n))
                    for c in _BK_COMPS:
                        cl = cells[_cell_id(c, cp[c])]
                        static_j[c][:, ai, pi, :] = cl["static"]
                        wake_events[c][:, ai, pi, :] = cl["wakes"]
                        setpm_by[c][:, ai, pi, :] = cl["setpm"]
                        gated_s[c][:, ai, pi, :] = cl["gated"]
                        dynamic_j[c][:, ai, pi, :] = dyn[c]
                        ov_total += cl["overhead"]
                    pol = cp["sram"]
                    static_j["sram"][:, ai, pi, :] = \
                        sram_static[pol.sram_state]
                    if pol.sram_state != "on":
                        gated_s["sram"][:, ai, pi, :] = sram_gu
                    if pol.sram_state in ("sleep", "off") and pol.mode == "sw":
                        setpm_by["sram"][:, ai, pi, :] = sram_setpm[:, None]
                    dynamic_j["sram"][:, ai, pi, :] = sram_dyn
                    static_j["other"][:, ai, pi, :] = \
                        pm.static_w["other"] * d_seg
                    dynamic_j["other"][:, ai, pi, :] = \
                        pm.dyn_max_w["other"] * 0.3 * d_seg
                    runtime[:, ai, pi, :] = d_seg + ov_total
    return result


def _validate_knob_grid(knob_grid) -> None:
    """Reject knob values that would silently corrupt the sweep:
    non-positive / non-finite delay scales flip gating inequalities,
    negative leak fractions produce negative energies, and a
    non-positive SA width breaks the occupancy model."""
    for i, k in enumerate(knob_grid):
        if not (np.isfinite(k.delay_scale) and k.delay_scale > 0):
            raise ValueError(
                f"knob {i}: delay_scale must be finite and > 0, got "
                f"{k.delay_scale!r}")
        if not (np.isfinite(k.window_scale) and k.window_scale > 0):
            raise ValueError(
                f"knob {i}: window_scale must be finite and > 0, got "
                f"{k.window_scale!r}")
        for fld in ("leak_off_logic", "leak_sram_sleep",
                    "leak_sram_off"):
            v = getattr(k, fld)
            if v is not None and not (np.isfinite(v) and v >= 0):
                raise ValueError(
                    f"knob {i}: {fld} must be finite and >= 0, got "
                    f"{v!r}")
        if k.sa_width is not None and int(k.sa_width) < 1:
            raise ValueError(
                f"knob {i}: sa_width must be >= 1, got {k.sa_width!r}")


def evaluate_batch(workloads, npus=("NPU-D",), policies=POLICIES,
                   knob_grid=None, *, backend: Optional[str] = None,
                   jax_mesh=None) -> BatchResult:
    """Every (workload × npu × policy × knob) cell through the sweep
    kernel (``_sweep_kernel``), one kernel call per NPU.

    The workloads are stacked into one ragged super-trace; service
    times, SA occupancy and idle-gap merges run once per unique SA width
    and (width, delay, window) triple inside the kernel, and component
    results are memoized per distinct ``_CompPolicy`` (ReGate-HW and
    ReGate-Full share the SA cell, ReGate-Base and ReGate-HW share
    VU/HBM/ICI/SRAM, …). Cell-for-cell equivalent to
    ``evaluate_reference`` to ≤1e-9 relative.

    ``backend`` selects the array substrate the kernel runs on:
    ``"numpy"`` (default — the kernel eagerly on the host) or ``"jax"``
    (one jitted program per stack shape, float64, reused across NPU
    generations). ``None`` resolves to the session default
    (``repro.core.backend.set_default_backend``). ``jax_mesh`` scales
    the jax path across devices (``parallel.jax_compat``; e.g.
    ``jax_compat.sweep_mesh``): a pure ``("wl",)`` mesh shards the
    stacked per-op arrays under GSPMD, while a mesh with a ``"knob"``
    axis — optionally crossed with ``"wl"`` — runs the explicit
    ``shard_map`` program that also shards the unique-width /
    (width, delay)-pair / knob axes (jax backend only).

    ``knob_grid`` accepts a ``KnobGrid`` (crossed via ``product()``), a
    flat sequence of ``PolicyKnobs``, or ``None`` (the single default
    point). ``backend=None`` / ``jax_mesh=None`` resolve through the
    active ``repro.core.session.SweepSession`` (the session mesh is
    only consulted when the effective backend is jax).
    """
    if isinstance(workloads, Workload):
        workloads = [workloads]
    workloads = list(workloads)
    npu_specs = tuple(get_npu(n) if isinstance(n, str) else n for n in npus)
    policies = tuple(policies)
    knob_grid = as_knob_tuple(knob_grid)
    _validate_knob_grid(knob_grid)
    backend = backend_mod.default_backend() if backend is None else backend
    if jax_mesh is None and backend != "numpy":
        from repro.core import session
        jax_mesh = session.resolve("jax_mesh")
    if jax_mesh is not None and backend == "numpy":
        raise ValueError("jax_mesh requires backend='jax'")
    bk = get_backend(backend)
    with bk.span("regate.evaluate_batch"):
        return _evaluate_batch_backend(workloads, npu_specs, policies,
                                       knob_grid, bk, mesh=jax_mesh)


def evaluate(wl: Workload, npu: NPUSpec | str = "NPU-D",
             policy: str = "ReGate-Full",
             knobs: PolicyKnobs = PolicyKnobs()) -> EnergyReport:
    """One cell of ``evaluate_batch`` on the host (numpy, whatever the
    session's backend); semantics identical to ``evaluate_reference``."""
    return evaluate_batch([wl], (npu,), (policy,), (knobs,),
                          backend="numpy").report(0, 0, 0, 0)


def evaluate_all(wl: Workload, npu="NPU-D",
                 knobs: PolicyKnobs = PolicyKnobs()) \
        -> dict[str, EnergyReport]:
    """All five policies for one workload — one ``evaluate_batch``
    call instead of five ``evaluate`` calls."""
    res = evaluate_batch(wl, (npu,), POLICIES, (knobs,))
    return {p: res.report(0, 0, pi, 0) for pi, p in enumerate(POLICIES)}


def savings_vs_nopg(reports: dict[str, EnergyReport]) -> dict[str, float]:
    base = reports["NoPG"].total_j
    return {p: 1.0 - r.total_j / base for p, r in reports.items()}
