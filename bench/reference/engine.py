"""Energy and runtime of one trace on one NPU under one gating design.

A per-op loop with the simulator's published semantics (ReGate, paper
§4 and §6): each op keeps every component busy for its own service time
and lasts as long as the slowest; idle time merges across ops; each
idle interval is priced by the design's gating mode:

* ``NoPG``        nothing gated;
* ``ReGate-Base`` hardware idle detection (window BET/3, exposed wake);
                  SRAM can only sleep;
* ``ReGate-HW``   + PE-level spatial SA gating;
* ``ReGate-Full`` + software ``setpm`` gating of the VU and SRAM;
* ``Ideal``       every idle cycle gated at no cost.

``record`` returns the simulator's sweep record for one cell. Every
number is carried in the float type ``f`` (``float`` for the reference;
the benchmark's control passes a narrower one).
"""
from __future__ import annotations

import math
from functools import lru_cache

from bench.reference import npu as hw

POLICIES = ("NoPG", "ReGate-Base", "ReGate-HW", "ReGate-Full", "Ideal")


@lru_cache(maxsize=None)
def sa_occupancy(M: int, K: int, N: int, saw: int) -> tuple:
    """PE-state occupancy of [M,K]x[K,N] on a weight-stationary SAW x SAW
    array: (duration cycles, ON, weight-only, OFF) PE-cycles, exact
    integers. Rows and columns beyond K and N are OFF; M rows stream
    diagonally through each live PE; weights load row by row."""
    kt, nt = math.ceil(K / saw), math.ceil(N / saw)
    k_last, n_last = K - (kt - 1) * saw, N - (nt - 1) * saw
    cyc = M + 2 * saw - 1 + saw
    live = ((kt - 1) * (nt - 1) * saw * saw + (kt - 1) * saw * n_last
            + (nt - 1) * k_last * saw + k_last * n_last)
    duration = kt * nt * cyc
    total = saw * saw * duration
    on = live * min(M, cyc)
    w_on = live * max(0, cyc - M)
    return duration, on, w_on, total - on - w_on, total


def op_times(op: dict, npu: dict, f=float) -> dict:
    """Per-component service times of one op instance, its duration and
    its SA occupancy fractions."""
    eff = f(1.0)
    occ = None
    if op["mm"] is not None:
        M, K, N = op["mm"]
        dur_cy, on, w_on, off, total = sa_occupancy(M, K, N,
                                                    npu["sa_width"])
        occ = (f(on) / f(total), f(w_on) / f(total), f(off) / f(total))
        if op["flops_sa"] > 0:
            flops_cycles = f(M * K) * f(N) / f(npu["sa_width"] ** 2)
            eff = max(min(f(1.0), flops_cycles / max(f(1e-9), f(dur_cy))),
                      f(1e-3))
    t = {"sa": f(op["flops_sa"]) / (npu["sa_flops"] * eff)
         if op["flops_sa"] > 0 else f(0.0),
         "vu": f(op["flops_vu"]) / npu["vu_flops"]
         if op["flops_vu"] > 0 else f(0.0),
         "hbm": f(op["bytes_hbm"]) / npu["hbm_bw"]
         if op["bytes_hbm"] > 0 else f(0.0),
         "ici": f(op["bytes_ici"]) / npu["ici_bw"]
         if op["bytes_ici"] > 0 else f(0.0)}
    t["max4"] = max(max(t["sa"], t["vu"]), max(t["hbm"], t["ici"]))
    t["dur"] = max(t["max4"], f(1e-12))
    t["occ"] = occ
    return t


def _modes(policy: str) -> dict:
    """Per component: (gating mode, gating-table key, spatial SA, SRAM
    state) of one design."""
    if policy == "NoPG":
        return {c: ("none", "", False, "on") for c in hw.COMPONENTS}
    if policy == "Ideal":
        d = {c: ("ideal", "", True, "ideal") for c in hw.COMPONENTS}
        d["other"] = ("none", "", False, "on")
        return d
    d = {"sa": ("hw", "sa_full", False, "on"), "vu": ("hw", "vu", False, "on"),
         "hbm": ("hw", "hbm", False, "on"), "ici": ("hw", "ici", False, "on"),
         "sram": ("hw", "sram_sleep", False, "sleep"),
         "other": ("none", "", False, "on")}
    if policy in ("ReGate-HW", "ReGate-Full"):
        d["sa"] = ("hw", "sa_pe", True, "on")
    if policy == "ReGate-Full":
        d["vu"] = ("sw", "vu", False, "on")
        d["sram"] = ("sw", "sram_off", False, "off")
    return d


def idle_energy(gap, p, mode, bet, delay, window, leak, f=float):
    """(energy, exposed wake, wakes, setpm, gated seconds) of one idle
    interval of ``gap`` seconds at static power ``p``."""
    zero = f(0.0)
    if gap <= 0:
        return zero, zero, zero, zero, zero
    if mode == "none":
        return p * gap, zero, zero, zero, zero
    if mode == "ideal":
        return zero, zero, zero, zero, gap
    if mode == "hw":
        if gap <= window:
            return p * gap, zero, zero, zero, zero
        gated = gap - window
        return (p * window + leak * p * gated + p * delay, delay, f(1.0),
                zero, gated)
    if gap >= max(bet, f(2.0) * delay):
        return (leak * p * (gap - 2 * delay) + p * 2 * delay, zero, f(1.0),
                f(2.0), gap - 2 * delay)
    return p * gap, zero, zero, zero, zero


def vu_bursts(t_vu, dur, npu, knobs, f=float):
    """The VU's slack inside a mixed op comes as one hole per burst of
    ``vu_burst_cycles`` (paper Fig 15): (slack s, bursts, gap cycles,
    BET cycles, delay cycles, window cycles)."""
    slack = dur - t_vu
    freq = npu["freq"]
    n_bursts = max(f(1.0), max(f(1.0), t_vu * freq)
                   / f(hw.GATING["vu_burst_cycles"]))
    gap_cy = slack * freq / n_bursts
    bet_cy = f(hw.BET["vu"]) * f(knobs["delay_scale"])
    delay_cy = f(hw.ON_OFF_DELAY["vu"]) * f(knobs["delay_scale"])
    window_cy = (bet_cy * f(hw.GATING["detection_window_frac"])
                 * f(knobs["window_scale"]))
    return slack, n_bursts, gap_cy, bet_cy, delay_cy, window_cy


def evaluate(ops: list[dict], npu_name: str, policy: str, knobs: dict,
             f=float) -> dict:
    """Runtime, per-component static and dynamic energy, wakes, gated
    seconds and setpm counts of ``ops`` on ``npu_name`` under
    ``policy`` at the knob point ``knobs`` (the §6.5 sensitivity axes:
    delay_scale, window_scale, sa_width and three gated-leakage
    overrides, None meaning the Table 3 value)."""
    npu = hw.npu(npu_name, knobs["sa_width"], f)
    g = hw.GATING
    modes = _modes(policy)

    def knob(name):
        v = knobs[name]
        return f(g[name] if v is None else v)

    leak_logic, leak_sleep, leak_off = (knob("leak_off_logic"),
                                        knob("leak_sram_sleep"),
                                        knob("leak_sram_off"))
    ds, ws, freq = f(knobs["delay_scale"]), f(knobs["window_scale"]), \
        npu["freq"]

    def delay_s(key):
        return f(hw.ON_OFF_DELAY.get(key, 0)) * ds / freq

    def bet_s(key):
        return f(hw.BET.get(key, 0)) * ds / freq

    def window_s(key):
        return bet_s(key) * f(g["detection_window_frac"]) * ws

    static_w, dyn_w = npu["static_w"], npu["dyn_w"]
    zero = f(0.0)
    static = {c: zero for c in hw.COMPONENTS}
    dynamic = {c: zero for c in hw.COMPONENTS}
    setpm = {c: zero for c in hw.COMPONENTS}
    gated = {c: zero for c in hw.COMPONENTS}
    wakes = {c: zero for c in hw.COMPONENTS}
    pending = {c: zero for c in hw.COMPONENTS}
    overhead = zero
    runtime = zero

    def leak_of(c):
        return max(leak_logic, f(g["leak_hbm_refresh"])) if c == "hbm" \
            else leak_logic

    def price(c, gap, n):
        nonlocal overhead
        mode, key = modes[c][0], modes[c][1]
        e, exposed, nw, sp, gs = idle_energy(
            gap, static_w[c], mode, bet_s(key), delay_s(key), window_s(key),
            leak_of(c), f)
        static[c] += e * n
        ov = exposed * n
        if c in ("hbm", "ici"):
            ov *= f(0.5)   # the wake overlaps the DMA issue latency
        overhead += ov
        setpm[c] += sp * n
        gated[c] += gs * n
        wakes[c] += nw * n

    def close_gap(c):
        gap = pending[c]
        pending[c] = zero
        if gap > 0:
            price(c, gap, f(1.0))

    prev_used = None
    for op in ops:
        t = op_times(op, npu, f)
        dur = t["dur"]
        n = f(op["count"])
        for c in ("sa", "vu", "hbm", "ici"):
            if t[c] > 0:
                close_gap(c)
        for c in ("sa", "vu", "hbm", "ici"):
            a = t[c]
            if a <= 0:
                pending[c] += dur * n
                continue
            mode, _key, spatial, _ = modes[c]
            if c == "sa":
                dynamic[c] += dyn_w[c] * (f(op["flops_sa"])
                                          / npu["sa_flops"]) * n
            else:
                dynamic[c] += dyn_w[c] * a * n
            if c == "sa" and spatial and t["occ"] is not None:
                on, w_on, off = t["occ"]
                occ = on + f(g["leak_pe_weight_on"]) * w_on \
                    + leak_logic * off
                if mode == "ideal":
                    occ = on
                static[c] += static_w[c] * occ * a * n
            else:
                static[c] += static_w[c] * a * n
            if c != "vu":
                slack = dur - a
                if slack > 0:
                    price(c, slack, n)
                continue
            # VU slack inside the op, one hole per burst
            slack, nb, gap_cy, bet_cy, delay_cy, window_cy = vu_bursts(
                a, dur, npu, knobs, f)
            if slack <= 0:
                continue
            p = static_w["vu"]
            if mode == "none":
                static["vu"] += p * slack * n
            elif mode == "ideal":
                gated["vu"] += slack * n
            elif mode == "hw":
                if gap_cy > bet_cy:
                    frac = max(zero, (gap_cy - window_cy) / gap_cy)
                    static["vu"] += p * slack * n * ((1 - frac)
                                                     + leak_logic * frac)
                    gated["vu"] += slack * n * frac
                    overhead += nb * delay_cy / freq * n
                    wakes["vu"] += nb * n
                else:
                    static["vu"] += p * slack * n
            else:
                if gap_cy >= max(bet_cy, 2 * delay_cy):
                    trans = 2 * delay_cy / gap_cy
                    static["vu"] += p * slack * n * (trans + leak_logic
                                                     * (1 - trans))
                    gated["vu"] += slack * n * (1 - trans)
                    setpm["vu"] += 2 * nb * n
                    wakes["vu"] += nb * n
                else:
                    static["vu"] += p * slack * n

        # SRAM: the used share is always on; the rest follows the design
        state, mode = modes["sram"][3], modes["sram"][0]
        used = min(f(1.0), f(op["sram_demand"]) / f(npu["sram_bytes"]))
        unused = 1 - used
        leak_unused = {"on": f(1.0), "sleep": leak_sleep,
                       "off": leak_off}.get(state, zero)
        static["sram"] += static_w["sram"] * dur * n * (
            used + unused * leak_unused)
        if state != "on":
            gated["sram"] += unused * dur * n
        if state in ("sleep", "off") and mode == "sw":
            # one range-setpm pair per change of the resident footprint
            if (used < 1 if prev_used is None else used != prev_used):
                setpm["sram"] += f(2.0)
        prev_used = used
        dynamic["sram"] += dyn_w["sram"] * t["max4"] * f(0.5) * n
        static["other"] += static_w["other"] * dur * n
        dynamic["other"] += dyn_w["other"] * dur * f(0.3) * n
        runtime += dur * n

    for c in ("sa", "vu", "hbm", "ici"):
        close_gap(c)
    return {"runtime_s": runtime + overhead, "static_j": static,
            "dynamic_j": dynamic, "wake_events": wakes, "gated_s": gated,
            "setpm_by": setpm, "freq": freq}


KNOB_COLUMNS = ("delay_scale", "leak_off_logic", "leak_sram_sleep",
                "leak_sram_off", "sa_width", "window_scale")


def record(workload: str, ops: list[dict], npu_name: str, policy: str,
           knobs: dict, knob_idx: int, f=float) -> dict:
    """The simulator's sweep record of one (workload, npu, policy, knob)
    cell: labels, the knob columns, totals and the per-component
    energies."""
    r = evaluate(ops, npu_name, policy, knobs, f)
    static_tot = sum(r["static_j"].values())
    dyn_tot = sum(r["dynamic_j"].values())
    total = static_tot + dyn_tot
    setpm = sum(r["setpm_by"].values())
    rt = r["runtime_s"]
    rec = {"workload": workload, "npu": npu_name, "policy": policy,
           "knob_idx": knob_idx,
           **{k: knobs[k] for k in KNOB_COLUMNS},
           "runtime_s": rt, "total_j": total,
           "static_total_j": static_tot, "dynamic_total_j": dyn_tot,
           "static_frac": static_tot / max(f(1e-12), total),
           "avg_power_w": total / max(f(1e-12), rt),
           "setpm_count": setpm,
           "setpm_per_1k_cycles": setpm / max(f(1.0), rt * r["freq"]) * 1e3,
           "wake_events": sum(r["wake_events"].values())}
    for c in hw.COMPONENTS:
        rec[f"static_j_{c}"] = r["static_j"][c]
    for c in hw.COMPONENTS:
        rec[f"dynamic_j_{c}"] = r["dynamic_j"][c]
    return {k: (float(v) if not isinstance(v, (str, int, type(None)))
                else v) for k, v in rec.items()}
