"""The modelled NPUs, their gating circuits and their power split.

A plain restatement of the published tables the simulator prices: the
paper's Table 2 (NPU-A..E), Table 3 (wake-up delays, break-even times,
gated leakage) and the per-generation power calibration. Kept here so
the benchmark's reference cannot move when the program's tables do.
"""
from __future__ import annotations

COMPONENTS = ("sa", "vu", "sram", "hbm", "ici", "other")
SRAM_SEGMENT_BYTES = 4 * 1024

ON_OFF_DELAY = {"sa_pe": 1, "sa_full": 10, "vu": 2, "hbm": 60, "ici": 60,
                "sram_sleep": 4, "sram_off": 10}
BET = {"sa_pe": 47, "sa_full": 469, "vu": 32, "hbm": 412, "ici": 459,
       "sram_sleep": 41, "sram_off": 82}
GATING = {"leak_off_logic": 0.03, "leak_sram_sleep": 0.25,
          "leak_sram_off": 0.002, "leak_hbm_refresh": 0.25,
          "vu_burst_cycles": 16, "leak_pe_weight_on": 0.15,
          "detection_window_frac": 1 / 3}

# name: (tech_nm, freq_mhz, sa_width, n_sa, n_vu, sram_mb, hbm_gbps,
#        ici_gbps_link, ici_links, idle_w, tdp_w)
_TABLE = {
    "NPU-A": (16, 700, 128, 2, 4, 32, 600, 62, 4, 53, 280),
    "NPU-B": (16, 940, 128, 4, 4, 32, 900, 70, 4, 84, 450),
    "NPU-C": (7, 1050, 128, 8, 4, 128, 1200, 50, 6, 55, 192),
    "NPU-D": (7, 1750, 128, 8, 6, 128, 2765, 100, 6, 90, 500),
    "NPU-E": (4, 2000, 256, 8, 8, 256, 7400, 150, 6, 130, 700),
}
NAMES = tuple(_TABLE)

STATIC_SHARES = {
    "NPU-A": {"sa": 0.080, "vu": 0.019, "sram": 0.154, "hbm": 0.224,
              "ici": 0.120, "other": 0.403},
    "NPU-B": {"sa": 0.090, "vu": 0.025, "sram": 0.170, "hbm": 0.200,
              "ici": 0.100, "other": 0.415},
    "NPU-C": {"sa": 0.100, "vu": 0.035, "sram": 0.220, "hbm": 0.120,
              "ici": 0.080, "other": 0.445},
    "NPU-D": {"sa": 0.110, "vu": 0.045, "sram": 0.220, "hbm": 0.100,
              "ici": 0.067, "other": 0.458},
    "NPU-E": {"sa": 0.140, "vu": 0.056, "sram": 0.244, "hbm": 0.090,
              "ici": 0.053, "other": 0.417},
}
DYN_SHARES = {"sa": 0.50, "vu": 0.12, "sram": 0.12, "hbm": 0.16,
              "ici": 0.04, "other": 0.06}
TEMP_UPLIFT = {16: 1.35, 7: 1.65, 4: 1.85}


def npu(name: str, sa_width=None, f=float) -> dict:
    """One NPU as a dict of plain numbers, with ``sa_width`` replaced
    when given. ``f`` is the float type every derived rate is held in
    (``float`` for the reference, a narrower type for its control)."""
    (tech, mhz, saw, n_sa, n_vu, sram_mb, hbm_gbps, ici_gbps, links,
     idle_w, tdp_w) = _TABLE[name]
    saw = int(sa_width) if sa_width is not None else saw
    freq = f(mhz * 1e6)
    static_busy = f(idle_w) * f(TEMP_UPLIFT[tech])
    dyn_tot = max(f(10.0), f(tdp_w) - static_busy)
    return {
        "name": name, "freq": freq, "sa_width": saw,
        "sa_flops": f(saw ** 2 * 2 * n_sa) * freq,
        "vu_flops": f(n_vu * 8 * 128 * 2) * freq,
        "hbm_bw": f(hbm_gbps * 1e9),
        "ici_bw": f(ici_gbps * links * 1e9),
        "sram_bytes": sram_mb * 2 ** 20,
        "sram_segments": sram_mb * 2 ** 20 // SRAM_SEGMENT_BYTES,
        "static_w": {c: static_busy * f(STATIC_SHARES[name][c])
                     for c in COMPONENTS},
        "dyn_w": {c: dyn_tot * f(DYN_SHARES[c]) for c in COMPONENTS},
    }
