"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` and
keeps three things, all on the profiler's one clock (nanoseconds):

* ``spans``: the benchmark's own host annotations (names starting with
  ``bench.``), as ``[name, start, end]``;
* ``modules``: per device, the XLA programs that ran (the device plane's
  ``XLA Modules`` line), as ``[name, start, end]``;
* ``ops``: per device, the XLA operations that ran (its ``XLA Ops``
  line), as ``[name, start, end]``; the name is the HLO instruction's
  (``%fusion.88``), without its shapes and operands.

``reduce`` turns that into what the readers in ``bench/metrics`` and the
result's ``breakdown`` take: the traced window, the device-busy union,
per-program device time, the top operations and the longest idle gaps,
each labelled by the innermost benchmark annotation open at its middle.
The loaded form is plain JSON, so the reduction is tested on a small
recorded trace without a chip.
"""
from __future__ import annotations

import re

WINDOW = "bench.window"


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, modules, ops = [], {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append([ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns])
        elif plane.name.startswith("/device:"):
            dev = plane.name
            for line in plane.lines:
                into = {"XLA Modules": modules, "XLA Ops": ops}.get(
                    line.name)
                if into is None:
                    continue
                into.setdefault(dev, []).extend(
                    [ev.name.split(" = ")[0], ev.start_ns,
                     ev.start_ns + ev.duration_ns] for ev in line.events)
    return {"spans": sorted(spans, key=lambda s: s[1]),
            "modules": modules, "ops": ops}


def union(intervals, lo=None, hi=None) -> list[list[float]]:
    """Merged ``[start, end]`` intervals, clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo) if lo is not None else s,
                        min(e, hi) if hi is not None else e)
                       for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def reduce(tr: dict, kernels: dict[str, str], top: int = 10) -> dict:
    """The traced window and what ran in it. ``kernels`` maps a kernel's
    stable name to the pattern of its XLA program name."""
    win = [s for s in tr["spans"] if s[0] == WINDOW]
    if not win:
        raise ValueError("trace holds no bench.window annotation")
    lo, hi = win[0][1], win[0][2]
    devs = sorted(set(tr["modules"]) | set(tr["ops"]))
    busy = {d: union(((s, e) for _n, s, e in
                      (tr["ops"].get(d) or tr["modules"].get(d, []))),
                     lo, hi) for d in devs}
    busy_ns = (sum(length(b) for b in busy.values()) / len(devs)
               if devs else 0.0)
    per_kernel = {}
    for kname, pattern in kernels.items():
        rx = re.compile(pattern)
        t = 0.0
        for d in devs:
            t += length(union(((s, e) for n, s, e in tr["modules"].get(d, [])
                               if rx.search(n)), lo, hi))
        if t > 0:
            per_kernel[kname] = t / max(1, len(devs))
    op_time: dict[str, float] = {}
    for d in devs:
        for n, s, e in tr["ops"].get(d, []):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                op_time[n] = op_time.get(n, 0.0) + (e - s)
    inner = [s for s in tr["spans"] if s[0] != WINDOW]
    gaps = []
    if devs:
        b = busy[devs[0]]
        edges = [lo] + [x for iv in b for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                mid = (s + e) / 2
                open_ = [sp for sp in inner if sp[1] <= mid < sp[2]]
                label = (min(open_, key=lambda sp: sp[2] - sp[1])[0]
                         if open_ else "no annotation")
                gaps.append([label, (e - s) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_ns": hi - lo, "busy_ns": busy_ns, "n_devices": len(devs),
        "kernel_ns": per_kernel,
        "busy": busy,
        "spans": inner,
        "queries": sum(1 for sp in inner if sp[0] == "bench.query"),
        "device_ops": sorted(([n, t / 1e9] for n, t in op_time.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": gaps[:top],
    }
