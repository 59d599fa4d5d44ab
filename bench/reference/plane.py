"""Software-managed gating of one trace, executed cycle by cycle.

The ReGate-Full machine runs the trace as a program: ops back to back on
a cycle schedule, one unit each for the SA, VU, DMA (HBM) and ICI. The
compiler pass places ``setpm`` pairs around every VU idle interval that
is worth gating (longer than the BET and than twice the wake delay) and
wakes the VU ``delay`` cycles early; the SA, DMA and ICI gate by
hardware idle detection. An event-driven executor then counts, per
unit, the cycles powered and gated, the wakes and the stalls. The VU's
holes inside mixed ops and the SRAM segment bands are folded in closed
form. ``record`` returns the simulator's program-plane record for one
(workload, npu, knob) cell, beside the closed-form ReGate-Full figures.
"""
from __future__ import annotations

import numpy as np

from bench.reference import engine
from bench.reference import npu as hw

UNITS = (("sa0", "sa", "sa_pe"), ("vu0", "vu", "vu"),
         ("dma0", "hbm", "hbm"), ("ici0", "ici", "ici"))
COMP_OF = {"sa0": "sa", "vu0": "vu", "dma0": "hbm", "ici0": "ici"}


def scaled_delay(key: str, ds: float) -> int:
    return int(round(hw.ON_OFF_DELAY[key] * ds))


def scaled_window(key: str, ds: float, ws: float) -> int:
    return max(8, int(hw.BET[key] * ds * hw.GATING["detection_window_frac"]
                      * ws))


def lower(ops: list[dict], npu: dict, f=float) -> dict:
    """Cycle schedule of the trace: per-instance start/end cycles, the
    uses of each unit, the SRAM demand per instance and the service
    times."""
    times = [engine.op_times(op, npu, f) for op in ops]
    inst = [i for i, op in enumerate(ops) for _ in range(int(op["count"]))]
    dtype = np.float64 if f is float else f
    dur = np.array([times[i]["dur"] for i in inst], dtype)
    edges = np.round(np.concatenate(([dtype(0.0)], np.cumsum(dur)))
                     * dtype(npu["freq"])).astype(np.int64)
    start, end = edges[:-1], edges[1:]
    uses = {}
    for unit, comp, _key in UNITS:
        us = []
        for j, i in enumerate(inst):
            d = int(end[j] - start[j])
            t_c = times[i][comp]
            if not (t_c > 0 and d > 0):
                continue
            if comp == "vu":     # bursts span the whole mixed op
                a = d
            else:
                a = min(d, max(1, int(np.round(t_c * npu["freq"]))))
            us.append((int(start[j]), a))
        uses[unit] = us
    return {"horizon": int(edges[-1]), "start": start, "end": end,
            "uses": uses, "times": times, "inst": inst,
            "demand": np.array([float(ops[i]["sram_demand"]) for i in inst])}


def vu_setpm(prog: dict, ds: float) -> list[tuple]:
    """(cycle, mode) setpm placements for the VU: OFF at the start of
    each idle interval worth gating, ON ``delay`` cycles before its end;
    an unused VU is gated for the whole program."""
    us = prog["uses"]["vu0"]
    if not us:
        ivs = [(0, prog["horizon"])]
    else:
        ivs = []
        if us[0][0] > 0:
            ivs.append((0, us[0][0]))
        for (c0, d0), (c1, _) in zip(us, us[1:]):
            if c1 > c0 + d0:
                ivs.append((c0 + d0, c1))
        tail = us[-1][0] + us[-1][1]
        if prog["horizon"] > tail:
            ivs.append((tail, prog["horizon"]))
    bet = hw.BET["vu"] * ds
    delay = scaled_delay("vu", ds)
    out = []
    for s, e in sorted(set(ivs)):
        n = e - s
        if n > bet and n > 2 * delay:
            out.append((s, "off"))
            out.append((e - delay, "on"))
    return out


def events(prog: dict, placements: list[tuple]) -> list[tuple]:
    """Sorted (cycle, bundle) events; a bundle maps unit -> latency and
    ``misc`` -> setpm mode. Same-mode setpms in one cycle merge; another
    slips to the next cycle (one misc slot per cycle)."""
    bundles: dict[int, dict] = {}
    for unit, us in prog["uses"].items():
        for c, d in us:
            bundles.setdefault(c, {})[unit] = d
    for c, mode in sorted(placements, key=lambda p: p[0]):
        c = max(0, c)
        while True:
            b = bundles.setdefault(c, {})
            if "misc" not in b:
                b["misc"] = mode
                break
            if b["misc"] == mode:
                break
            c += 1
    return sorted(bundles.items())


def execute(evs: list[tuple], horizon: int, ds: float, ws: float) -> dict:
    """Run the event program on the ReGate-Full machine: the VU starts
    software-managed (ON), the rest under hardware idle detection; a
    dispatch to a gated unit wakes it and stalls; a powered AUTO unit
    gates once it has idled for its detection window."""
    st = {}
    for unit, _comp, key in UNITS:
        st[unit] = {"powered": True, "mode": "on" if unit == "vu0"
                    else "auto", "ready": 0, "busy": 0, "idle": 0,
                    "on": 0, "gated": 0, "wakes": 0,
                    "delay": scaled_delay(key, ds),
                    "window": scaled_window(key, ds, ws)}
    t = 0
    stalls = 0

    def gap(n, t):
        for u in st.values():
            if not u["powered"]:
                u["gated"] += n
            elif u["mode"] != "auto":
                u["on"] += n
            else:
                g = max(u["idle"] + u["window"], u["busy"])
                on = min(max(g - t - 1, 0), n)
                u["on"] += on
                if n > on:
                    u["gated"] += n - on
                    u["powered"] = False

    prev = -1
    for idx, bundle in evs:
        if idx - prev - 1:
            gap(idx - prev - 1, t)
            t += idx - prev - 1
        mode = bundle.get("misc")      # a setpm addresses the VU only
        if mode is not None:
            u = st["vu0"]
            u["mode"] = mode
            if mode == "off":
                u["powered"] = False
            elif not u["powered"]:
                u["powered"] = True
                u["ready"] = t + u["delay"]
                u["wakes"] += 1
        start = t
        for unit in bundle:
            if unit == "misc":
                continue
            u = st[unit]
            if not u["powered"]:
                u["powered"] = True
                u["ready"] = max(t, u["busy"]) + u["delay"]
                u["wakes"] += 1
            start = max(start, u["ready"], u["busy"])
        stalls += start - t
        for unit, lat in bundle.items():
            if unit == "misc":
                continue
            st[unit]["busy"] = start + lat
            st[unit]["idle"] = start + lat
        t = start + 1
        for u in st.values():
            if (u["powered"] and u["mode"] == "auto"
                    and t - u["idle"] >= u["window"] and u["busy"] <= t):
                u["powered"] = False
        for u in st.values():
            if u["powered"]:
                u["on"] += 1
            else:
                u["gated"] += 1
        prev = idx
    if horizon > prev + 1:
        gap(horizon - prev - 1, t)
        t += horizon - prev - 1
    end = max([t] + [u["busy"] for u in st.values()])
    for u in st.values():
        if u["powered"]:
            u["on"] += end - t
        else:
            u["gated"] += end - t
    return {"cycles": end, "stall_cycles": stalls,
            "gated": {COMP_OF[k]: u["gated"] for k, u in st.items()},
            "wakes": {COMP_OF[k]: u["wakes"] for k, u in st.items()}}


def vu_fold(prog: dict, ops: list[dict], npu: dict, knobs: dict,
            f=float) -> dict:
    """The VU's holes inside mixed ops under software gating, summed
    over the trace's ops in order: gated cycles, setpm and wakes."""
    gated, sp, wk = [], [], []
    for op, t in zip(ops, prog["times"]):
        if not t["vu"] > 0:
            continue
        slack, nb, gap_cy, bet_cy, delay_cy, _w = engine.vu_bursts(
            t["vu"], t["dur"], npu, knobs, f)
        if not slack > 0:
            continue
        n = f(op["count"])
        on = gap_cy >= max(bet_cy, 2 * delay_cy)
        trans = 2 * delay_cy / gap_cy if gap_cy > 0 else f(0.0)
        gated.append(slack * n * (1 - trans) if on else f(0.0))
        sp.append(2 * nb * n if on else f(0.0))
        wk.append(nb * n if on else f(0.0))

    def total(xs):
        return f(np.sum(np.array(xs, np.float64 if f is float else f))) \
            if xs else f(0.0)
    return {"gated_s": total(gated), "setpm": total(sp), "wakes": total(wk)}


def sram_bands(prog: dict, npu: dict, ds: float) -> dict:
    """SRAM segments grouped by the demand values that bound them: a
    segment is live while an op's resident demand reaches above it, and
    a dead interval worth gating is gated (2x the wake delay at full
    power); bands that share a dead interval share one setpm pair."""
    n_seg, seg = npu["sram_segments"], hw.SRAM_SEGMENT_BYTES
    horizon = prog["horizon"]
    bet = hw.BET["sram_off"] * ds
    delay = hw.ON_OFF_DELAY["sram_off"] * ds
    d = np.minimum(prog["demand"], n_seg * seg)
    gated = 0.0
    keys = set()
    dead_band = False
    if len(d) == 0 or horizon == 0:
        return {"gated_segcycles": 0.0, "setpm": 0.0, "n_segments": n_seg}
    vals = np.unique(d)
    lows = np.concatenate(([0.0], vals))
    highs = np.concatenate((vals, [float(n_seg) * seg]))
    for lo, hi in zip(lows, highs):
        width = min(int(np.ceil(hi / seg)), n_seg) - int(np.ceil(lo / seg))
        if width <= 0:
            continue
        busy = np.flatnonzero(d >= hi)
        if hi > vals[-1] or busy.size == 0:
            gated += float(width) * horizon
            dead_band = True
            continue
        bs = np.concatenate(([0], prog["end"][busy]))
        be = np.concatenate((prog["start"][busy], [horizon]))
        gaps = (be - bs).astype(np.float64)
        gate = (gaps > bet) & (gaps > 2 * delay)
        if gate.any():
            gated += float(width) * float((gaps[gate] - 2 * delay).sum())
            keys.update(zip(bs[gate].tolist(), be[gate].tolist()))
    return {"gated_segcycles": gated,
            "setpm": 2.0 * len(keys) + (1.0 if dead_band else 0.0),
            "n_segments": n_seg}


def record(workload: str, ops: list[dict], npu_name: str, knobs: dict,
           knob_idx: int, f=float) -> dict:
    """The program-plane record of one (workload, npu, knob) cell."""
    npu = hw.npu(npu_name, knobs["sa_width"], f)
    ds, ws = knobs["delay_scale"], knobs["window_scale"]
    prog = lower(ops, npu, f)
    placements = vu_setpm(prog, ds)
    evs = events(prog, placements)
    ex = execute(evs, prog["horizon"], ds, ws)
    fold = vu_fold(prog, ops, npu, knobs, f)
    sb = sram_bands(prog, npu, ds)
    freq = npu["freq"]
    gated = {c: f(v) for c, v in ex["gated"].items()}
    wakes = {c: f(v) for c, v in ex["wakes"].items()}
    gated["vu"] = gated["vu"] + fold["gated_s"] * freq
    wakes["vu"] = wakes["vu"] + fold["wakes"]
    gated["sram"] = f(sb["gated_segcycles"]) / max(1, sb["n_segments"])
    setpm = {"vu": f(len(placements)) + fold["setpm"],
             "sram": f(sb["setpm"])}
    pol = engine.evaluate(ops, npu_name, "ReGate-Full", knobs, f)
    rt_cy = freq * pol["runtime_s"]
    cycles = max(1, ex["cycles"])
    rec = {"workload": workload, "npu": npu_name, "policy": "ReGate-Full",
           "knob_idx": knob_idx,
           **{k: knobs[k] for k in engine.KNOB_COLUMNS},
           "prog_cycles": int(ex["cycles"]), "policy_cycles": rt_cy,
           "runtime_rel_err": abs(ex["cycles"] - rt_cy) / max(f(1.0), rt_cy),
           "n_events": len(evs), "stall_cycles": int(ex["stall_cycles"])}
    for c in ("sa", "vu", "hbm", "ici", "sram"):
        pol_frac = pol["gated_s"][c] / max(f(1e-30), pol["runtime_s"])
        frac = gated[c] / cycles
        rec[f"gated_frac_policy_{c}"] = pol_frac
        rec[f"gated_frac_prog_{c}"] = frac
        rec[f"gated_frac_absdiff_{c}"] = abs(frac - pol_frac)
        rec[f"gated_s_prog_{c}"] = gated[c] / freq
    for c in ("sa", "vu", "hbm", "ici"):
        rec[f"wakes_prog_{c}"] = wakes[c]
    for c in ("vu", "sram"):
        rec[f"setpm_policy_{c}"] = pol["setpm_by"][c]
        rec[f"setpm_prog_{c}"] = setpm[c]
    return {k: (float(v) if not isinstance(v, (str, int, type(None)))
                else v) for k, v in rec.items()}
