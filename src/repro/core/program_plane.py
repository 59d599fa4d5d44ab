"""Batched program plane (ISSUE 10): software-managed gating on arrays.

The software-managed half of ReGate (§5.3/Fig 14: compiler-placed
``setpm`` driving the VU, plus SRAM segment bands) used to be evaluated
one (workload, npu) cell at a time on the host event-driven executor —
``sweep_program_plane`` was a bare Python double loop over
``crossval_record``. This module lowers the instrumented programs into
one ragged columnar stack and executes ALL cells in lock-step through
the array backend, so the program plane rides numpy *and* jax exactly
like ``policies.evaluate_batch``:

* ``build_program_arrays`` compiles each lowered program
  (``lowering.lower_workload`` SlotUse timelines + the §4.3
  ``instrument_setpm`` placements, merged by ``lowering.build_events``)
  into a ``ProgramArrays`` stack — concatenated per-event columns
  (cycle index, per-unit issue latencies, per-unit setpm effects) with
  ``offsets``/``seg_ids`` per the ``opgen.StackedTrace`` convention.
  Instrumentation re-placement happens once per unique
  ``delay_scale`` (the PR-4 unique-pair trick): window/leak knob
  points sharing a delay scale share event streams.
* ``_exec_kernel`` is the batched executor: one backend-neutral
  ``scan`` over the padded event axis whose carry holds the whole
  (row, unit) machine state — power, mode, ready/busy/idle cycles and
  the on/gated accounting. Each scan step replays ``EventTimeline``'s
  closed-form gap handling plus the bundle step (setpm, structural
  hazards with auto-wake, issue, idle-detection window crossing) as
  pure integer array ops, so the batched results equal the event-driven
  executor's EXACTLY, including the cross-unit stall coupling — per
  cell, bit for bit, on numpy and on jitted jax (int64 under the x64
  scope). The BET/window knobs enter as per-row integer delay/window
  parameters computed by the same ``isa.scaled_delay`` /
  ``isa.scaled_window`` helpers the executors use.
* ``program_plane_batch`` assembles the full (workload x npu x knob)
  cube: kernel outputs, the closed-form intra-op VU burst fold and the
  SRAM band analysis (both once per unique knob pair), and the
  closed-form ``ReGate-Full`` policy side via one ``evaluate_batch``
  call. ``sweep_program_plane`` (``repro.core.sweep``) is a thin
  wrapper emitting one ``lowering.plane_record`` per cell.

With ``jax_mesh`` (a mesh with a ``"wl"`` axis) the dense event stack
is device_put sharded along the row axis — rows are independent, so
GSPMD splits the scan across devices with no cross-device traffic;
inert padding rows (horizon 0, no events) make the row count divisible.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Iterable, Optional, Sequence

import numpy as np

from repro.core import session
from repro.core.backend import (get_backend, put_slabs, transfer_counts,
                                unpack_slabs)
from repro.core.hw import NPUSpec, get_npu, with_sa_width
from repro.core.isa import events_to_arrays, scaled_delay, scaled_window
from repro.core.lowering import (COMP_OF_UNIT, REGATE_FULL_TIMELINE,
                                 UNIT_OF, LoweredProgram, build_events,
                                 instrument_program, lower_workload,
                                 plane_record, sram_band_gating)
from repro.core.opgen import Workload
from repro.core.policies import (BatchResult, PolicyKnobs,
                                 _component_policies,
                                 _fine_grained_vu_vec, evaluate_batch,
                                 knob_pairs)

# fixed kernel unit order; component order follows UNIT_OF
UNITS = tuple(u for u, _ in UNIT_OF.values())          # sa0 vu0 dma0 ici0
COMPS = tuple(COMP_OF_UNIT[u] for u in UNITS)          # sa  vu  hbm  ici
# gating-table key per unit under the ReGate-Full machine (the
# delay_keys override in REGATE_FULL_TIMELINE: SA wakes at PE grain)
_TABLE_KEY = {"sa": "sa_pe", "vu": "vu", "hbm": "hbm", "ici": "ici"}
_KEYS = tuple(_TABLE_KEY[c] for c in COMPS)
# initial power modes (mode codes: 0 AUTO, 1 ON, 2 OFF): the
# software-managed VU starts ON, everything else under hw detection
_MODE0 = tuple(1 if UNITS[i] in REGATE_FULL_TIMELINE["initial_modes"]
               else 0 for i in range(len(UNITS)))


@dataclass
class ProgramArrays:
    """Ragged columnar stack of instrumented event programs.

    Stream ``s`` owns rows ``offsets[s]:offsets[s+1]`` of the
    concatenated event columns (the ``StackedTrace`` convention);
    ``seg_ids`` is the equivalent per-event stream id."""
    units: tuple[str, ...]
    cycle: np.ndarray          # (N,)  event cycle indices, int64
    lat: np.ndarray            # (N,U) per-unit issue latency (0 unused)
    pm: np.ndarray             # (N,U) setpm effect codes (isa.PM_*)
    offsets: np.ndarray        # (S+1,)
    horizon: np.ndarray        # (S,)
    setpm_vu: np.ndarray       # (S,) §4.3 placement count (VU)

    @property
    def n_streams(self) -> int:
        return len(self.offsets) - 1

    @property
    def lengths(self) -> np.ndarray:
        return self.offsets[1:] - self.offsets[:-1]

    @property
    def seg_ids(self) -> np.ndarray:
        return np.repeat(np.arange(self.n_streams, dtype=np.int64),
                         self.lengths)


# per-(program, delay_scale) columnar event stream, FIFO-bounded like
# the instrumentation cache (strong prog ref keeps the id valid)
_STREAM_CACHE: dict[tuple[int, float], tuple[LoweredProgram, dict]] = {}
_STREAM_CACHE_MAX = 256


def _stream_arrays(prog: LoweredProgram, dscale: float) -> dict:
    key = (id(prog), float(dscale))
    hit = _STREAM_CACHE.get(key)
    if hit is not None and hit[0] is prog:
        return hit[1]
    placements = instrument_program(prog, delay_scale=dscale)
    events = build_events(prog, placements)
    arr = events_to_arrays(events, UNITS)
    arr["horizon"] = int(prog.horizon)
    arr["setpm_vu"] = float(len(placements))
    if len(_STREAM_CACHE) >= _STREAM_CACHE_MAX:
        _STREAM_CACHE.pop(next(iter(_STREAM_CACHE)))
    _STREAM_CACHE[key] = (prog, arr)
    return arr


def build_program_arrays(progs: Sequence[LoweredProgram],
                         dscales: Sequence[float]) -> ProgramArrays:
    """Stack one instrumented event stream per (program, delay_scale)
    pair into a ragged ``ProgramArrays``."""
    streams = [_stream_arrays(p, d) for p, d in zip(progs, dscales)]
    lengths = np.array([len(s["cycle"]) for s in streams], np.int64)
    offsets = np.zeros(len(streams) + 1, np.int64)
    np.cumsum(lengths, out=offsets[1:])
    u = len(UNITS)
    return ProgramArrays(
        units=UNITS,
        cycle=np.concatenate([s["cycle"] for s in streams])
        if streams else np.zeros(0, np.int64),
        lat=np.concatenate([s["lat"] for s in streams])
        if streams else np.zeros((0, u), np.int64),
        pm=np.concatenate([s["pm"] for s in streams])
        if streams else np.zeros((0, u), np.int8),
        offsets=offsets,
        horizon=np.array([s["horizon"] for s in streams], np.int64),
        setpm_vu=np.array([s["setpm_vu"] for s in streams], np.float64))


# --------------------------------------------------------------------------
# the batched executor kernel
# --------------------------------------------------------------------------

def _pack_dense(pa: ProgramArrays, stream_of_row: np.ndarray,
                window: np.ndarray, delay: np.ndarray,
                horizon: np.ndarray) -> dict:
    """Gather the ragged stack into the kernel's dense (E, R[, U])
    layout; padded events carry cycle -1 (the in-kernel no-op mask)."""
    u = len(pa.units)
    lens = pa.lengths[stream_of_row]
    r = len(stream_of_row)
    e_max = int(lens.max()) if r else 0
    cycle = np.full((e_max, r), -1, np.int64)
    lat = np.zeros((e_max, r, u), np.int64)
    pm = np.zeros((e_max, r, u), np.int8)
    for ri, s in enumerate(stream_of_row):
        lo, hi = pa.offsets[s], pa.offsets[s + 1]
        n = hi - lo
        cycle[:n, ri] = pa.cycle[lo:hi]
        lat[:n, ri] = pa.lat[lo:hi]
        pm[:n, ri] = pa.pm[lo:hi]
    return {"cycle": cycle, "lat": lat, "pm": pm,
            "delay": delay.astype(np.int64),
            "window": window.astype(np.int64),
            "mode0": np.broadcast_to(
                np.array(_MODE0, np.int64), (r, u)).copy(),
            "horizon": horizon.astype(np.int64)}


def _kernel_body(data, xp):
    """The lock-step event executor: ``EventTimeline`` semantics with
    the (row, unit) axes vectorized. Integer arithmetic throughout —
    results are exactly the per-cell executor's."""
    delay, window = data["delay"], data["window"]
    r, u = delay.shape
    zeros = xp.zeros((r, u), xp.int64)

    def gap_account(st, n):
        """Closed-form ``_gap(n, t)``: powered AUTO units cross their
        idle-detection window mid-gap and count gated from there."""
        powered, auto = st["powered"], st["mode"] == 0
        g = xp.maximum(st["idle"] + window, st["busy"])
        n_u = n[:, None]
        on_gap = xp.clip(g - st["t"][:, None] - 1, 0, n_u)
        on_add = xp.where(powered, xp.where(auto, on_gap, n_u), 0)
        gate_add = n_u - on_add
        crossed = auto & powered & (gate_add > 0)
        return dict(st, powered=powered & ~crossed,
                    on=st["on"] + on_add,
                    gated=st["gated"] + gate_add, t=st["t"] + n)

    def step(st, x):
        cyc, lat, pm = x["cycle"], x["lat"], x["pm"]
        valid = cyc >= 0
        g1 = gap_account(st, xp.maximum(cyc - st["prev"] - 1, 0))
        t1 = g1["t"]
        t1_u = t1[:, None]
        # misc-slot setpm applies first (takes effect this cycle)
        powered, mode = g1["powered"], g1["mode"]
        ready, wakes = g1["ready"], g1["wakes"]
        is_on, is_off, is_auto = pm == 1, pm == 2, pm == 3
        wake_pm = is_on & ~powered
        ready = xp.where(wake_pm, t1_u + delay, ready)
        wakes = wakes + wake_pm
        powered = (powered | wake_pm) & ~is_off
        mode = xp.where(is_on, 1, xp.where(is_off, 2,
                                           xp.where(is_auto, 0, mode)))
        nsetpm_add = (pm > 0).any(axis=1)
        # structural hazards: auto-wake on dispatch, wait for ready/busy
        ref = lat > 0
        wake_d = ref & ~powered
        ready = xp.where(wake_d, xp.maximum(t1_u, g1["busy"]) + delay,
                         ready)
        wakes = wakes + wake_d
        powered = powered | wake_d
        need = xp.where(ref, xp.maximum(ready, g1["busy"]), 0)
        start = xp.maximum(t1, need.max(axis=1))
        # issue
        busy = xp.where(ref, start[:, None] + lat, g1["busy"])
        idle = xp.where(ref, busy, g1["idle"])
        t2 = start + 1
        t2_u = t2[:, None]
        # hardware idle-detection gating at the post-issue cycle
        gate4 = (powered & (mode == 0) & (t2_u - idle >= window)
                 & (busy <= t2_u))
        powered = powered & ~gate4
        new = dict(
            t=t2, prev=cyc, powered=powered, mode=mode, ready=ready,
            busy=busy, idle=idle, on=g1["on"] + powered,
            gated=g1["gated"] + ~powered, wakes=wakes,
            stalls=g1["stalls"] + (start - t1),
            nsetpm=g1["nsetpm"] + nsetpm_add)
        v_u = valid[:, None]
        return {k: xp.where(valid if v.ndim == 1 else v_u, v, st[k])
                for k, v in new.items()}

    init = dict(
        t=xp.zeros(r, xp.int64), prev=xp.full(r, -1, xp.int64),
        powered=xp.ones((r, u), bool), mode=data["mode0"],
        ready=zeros, busy=zeros, idle=zeros, on=zeros, gated=zeros,
        wakes=zeros, stalls=xp.zeros(r, xp.int64),
        nsetpm=xp.zeros(r, xp.int64))
    return init, gap_account, step


# the dense leaves whose last axis is the unit axis (U = 4). They ride
# their slab unit-major, last two axes swapped: the chip tiles an
# array's two minor axes, and a 4-wide minor axis rebuilt from a flat
# slab pads to the tile's 128 lanes (32x the bytes, and over a minute
# of compile at the paper suite's stack)
_UNIT_MINOR = ("lat", "pm", "delay", "window", "mode0")


def _unit_major(d: dict) -> dict:
    """``d`` with the last two axes of its ``_UNIT_MINOR`` leaves
    swapped: the order they are put in, and back (its own inverse)."""
    return {k: v.swapaxes(-1, -2) if k in _UNIT_MINOR else v
            for k, v in d.items()}


def _full_body(bk):
    """The jit'able whole-stack program: scan over the event axis, then
    the ``run()`` tail gap to the horizon and ``_finish``'s drain.
    ``layout`` (static) is the ``slab_layout`` where ``d`` comes as the
    ``put_slabs`` slabs of ``_unit_major(d)``, ``None`` where it comes as
    the per-leaf ``_pack_dense`` dict (the mesh path)."""
    xp = bk.xp

    def body(d, layout=None):
        if layout is not None:
            d = _unit_major(unpack_slabs(d, layout))
        init, gap_account, step = _kernel_body(d, xp)
        st = bk.scan(step, init,
                     {"cycle": d["cycle"], "lat": d["lat"],
                      "pm": d["pm"]}, length=d["cycle"].shape[0])
        st = gap_account(st,
                         xp.maximum(d["horizon"] - st["prev"] - 1, 0))
        end = xp.maximum(st["t"], st["busy"].max(axis=1))
        extra = (end - st["t"])[:, None]
        return {"cycles": end, "stall_cycles": st["stalls"],
                "on": st["on"] + xp.where(st["powered"], extra, 0),
                "gated": st["gated"] + xp.where(st["powered"], 0, extra),
                "wakes": st["wakes"], "setpm_executed": st["nsetpm"]}

    return body


def _compiled(bk):
    fn = _KERNELS.get(bk.name)
    if fn is None:
        fn = bk.jit(_full_body(bk), static_argnames=("layout",))
        _KERNELS[bk.name] = fn
    return fn


def _scan_counts(data: dict) -> dict:
    """The ``regate.scan_kernel`` span's stats: rows scanned, the
    padded event depth and the real events summed over rows."""
    cycle = data["cycle"]
    return {"rows": cycle.shape[1], "e_max": cycle.shape[0],
            "events": int(np.count_nonzero(cycle >= 0))}


def _run_kernel(data: dict, bk) -> dict[str, np.ndarray]:
    """Execute the packed event stack on the backend, put unit-major as
    one int64 and one int8 (``pm``) slab; returns host numpy outputs per
    row."""
    fn = _compiled(bk)
    with bk.compute_scope():
        layout, dev = put_slabs(_unit_major(data), bk)
        with bk.span("regate.scan_kernel", lambda: _scan_counts(data)):
            out = bk.block(fn(dev, layout=layout))
    with bk.span("regate.harvest", lambda: transfer_counts(out)):
        return {k: bk.to_numpy(v) for k, v in out.items()}


_KERNELS: dict[str, object] = {}


def _mesh_pad(data: dict, n_dev: int) -> tuple[dict, int]:
    """Pad the row axis to a multiple of the mesh size with inert rows
    (horizon 0, no events) so the sharded axes divide evenly."""
    r = data["horizon"].shape[0]
    pad = (-r) % n_dev
    if pad == 0:
        return data, r
    out = {}
    for k, v in data.items():
        axis = 1 if k in ("cycle", "lat", "pm") else 0
        widths = [(0, 0)] * v.ndim
        widths[axis] = (0, pad)
        fill = -1 if k == "cycle" else 0
        out[k] = np.pad(v, widths, constant_values=fill)
    return out, r


def _run_kernel_mesh(data: dict, bk, mesh) -> dict[str, np.ndarray]:
    """Mesh path: device_put the dense stack sharded along the row axis
    of a ``("wl",)`` mesh; rows are independent, so GSPMD executes the
    scan shard-locally."""
    n_dev = int(np.prod(list(bk.mesh_axis_sizes(mesh).values())))
    padded, r = _mesh_pad(data, n_dev)
    fn = _compiled(bk)
    with bk.compute_scope():
        from jax.sharding import NamedSharding
        put = {}
        for k, v in padded.items():
            spec = (bk.pspec(None, "wl") if k in ("cycle", "lat", "pm")
                    else bk.pspec("wl"))
            put[k] = bk._jax.device_put(
                bk.asarray(v), NamedSharding(mesh, spec))
        out = bk.block(fn(put))
    return {k: bk.to_numpy(v)[:r] for k, v in out.items()}


# --------------------------------------------------------------------------
# the batched plane: cube assembly + records
# --------------------------------------------------------------------------

@dataclass
class ProgramPlaneBatch:
    """The full (workload x npu x knob) program-plane cube.

    Executor-side arrays are indexed (W, A, T) over the unique knob
    triples; ``records()`` expands to the full knob axis via ``inv``
    and assembles one ``lowering.plane_record`` per cell."""
    workloads: tuple[str, ...]
    npus: tuple[NPUSpec, ...]
    knob_grid: tuple[PolicyKnobs, ...]
    triples: list[tuple]
    inv: np.ndarray                       # (K,) knob -> triple index
    cycles: np.ndarray                    # (W, A, T) int64
    stall_cycles: np.ndarray              # (W, A, T) int64
    n_events: np.ndarray                  # (W, A, T) int64
    gated_cycles: dict[str, np.ndarray]   # comp -> (W, A, T) float64
    wake_events: dict[str, np.ndarray]    # comp -> (W, A, T) float64
    setpm_isa: dict[str, np.ndarray]      # vu/sram -> (W, A, T)
    policy: BatchResult = field(repr=False)

    def records(self) -> list[dict]:
        """Flat records, workload-major then NPU then knob index — the
        sweep convention, one record per (workload, npu, knob) cell."""
        recs = []
        pol = self.policy
        for wi, wl in enumerate(self.workloads):
            for ai, npu in enumerate(self.npus):
                for ki, knobs in enumerate(self.knob_grid):
                    ti = int(self.inv[ki])
                    c = (wi, ai, ti)
                    recs.append(plane_record(
                        wl, npu, knobs, ki,
                        prog={
                            "cycles": int(self.cycles[c]),
                            "n_events": int(self.n_events[c]),
                            "stall_cycles": int(self.stall_cycles[c]),
                            "gated_cycles": {
                                k: float(v[c])
                                for k, v in self.gated_cycles.items()},
                            "wake_events": {
                                k: float(v[c])
                                for k, v in self.wake_events.items()},
                            "setpm_isa": {
                                k: float(v[c])
                                for k, v in self.setpm_isa.items()}},
                        policy={
                            "runtime_s":
                                float(pol.runtime_s[wi, ai, 0, ki]),
                            "gated_s": {
                                k: float(v[wi, ai, 0, ki])
                                for k, v in pol.gated_s.items()},
                            "setpm_by": {
                                k: float(v[wi, ai, 0, ki])
                                for k, v in pol.setpm_by.items()}}))
        return recs


def _exec_rows(workloads: Sequence[Workload],
               npu_specs: Sequence[NPUSpec], triples: list[tuple]) \
        -> tuple[ProgramArrays, np.ndarray, dict]:
    """The executor rows of a (workload x npu x knob-triple) cube, row
    ``(wi * A + ai) * T + ti``: the ragged event stack, each row's
    stream, and the dense kernel input."""
    w_n, a_n, t_n = len(workloads), len(npu_specs), len(triples)

    # one lowered program per (workload, effective npu); one event
    # stream per (program, delay_scale) — all identity-cached
    stream_index: dict[tuple, int] = {}
    progs: list[LoweredProgram] = []
    dscales: list[float] = []
    stream_of_row = np.empty(w_n * a_n * t_n, np.int64)
    window = np.empty((w_n * a_n * t_n, len(UNITS)), np.int64)
    delay = np.empty_like(window)
    horizon = np.empty(w_n * a_n * t_n, np.int64)
    for wi, wl in enumerate(workloads):
        for ai, npu in enumerate(npu_specs):
            for ti, (saw, dsc, wsc) in enumerate(triples):
                npu_eff = with_sa_width(npu, saw)
                prog = lower_workload(wl, npu_eff)
                skey = (id(prog), float(dsc))
                si = stream_index.get(skey)
                if si is None:
                    si = len(progs)
                    stream_index[skey] = si
                    progs.append(prog)
                    dscales.append(float(dsc))
                ri = (wi * a_n + ai) * t_n + ti
                stream_of_row[ri] = si
                horizon[ri] = prog.horizon
                g = npu_eff.gating
                for ui, key in enumerate(_KEYS):
                    delay[ri, ui] = scaled_delay(g, key, dsc)
                    window[ri, ui] = scaled_window(g, key, dsc, wsc)

    pa = build_program_arrays(progs, dscales)
    return pa, stream_of_row, _pack_dense(pa, stream_of_row, window,
                                          delay, horizon)


def program_plane_batch(workloads: Sequence[Workload] | Workload,
                        npus: Iterable[NPUSpec | str] = ("NPU-D",),
                        knob_grid: Optional[Sequence[PolicyKnobs]] = None,
                        backend: Optional[str] = None,
                        jax_mesh=None) -> ProgramPlaneBatch:
    """Evaluate the program plane for every (workload, npu, knob) cell
    through the batched executor kernel + the closed-form folds.

    Matches the per-cell ``lowering.crossval_record`` record-for-record:
    executor integers exactly, closed-form folds bit-identically (same
    host functions), the policy side within ``evaluate_batch``'s
    documented <=1e-9 of per-cell ``evaluate``."""
    if isinstance(workloads, Workload):
        workloads = [workloads]
    workloads = list(workloads)
    npu_specs = [get_npu(n) if isinstance(n, str) else n for n in npus]
    grid = tuple(knob_grid) if knob_grid is not None else (PolicyKnobs(),)
    bk = get_backend(backend)
    if jax_mesh is None and bk.name == "jax":
        jax_mesh = session.resolve("jax_mesh")

    triples, inv = knob_pairs(grid)
    w_n, a_n, t_n = len(workloads), len(npu_specs), len(triples)
    with bk.span("regate.program_plane_batch"):
        with bk.span("regate.exec_rows"):
            pa, stream_of_row, data = _exec_rows(workloads, npu_specs,
                                                 triples)
        if jax_mesh is not None and bk.name == "jax" \
                and "wl" in bk.mesh_axis_sizes(jax_mesh):
            out = _run_kernel_mesh(data, bk, jax_mesh)
        else:
            out = _run_kernel(data, bk)

        shape = (w_n, a_n, t_n)
        cycles = out["cycles"].reshape(shape)
        stalls = out["stall_cycles"].reshape(shape)
        gated_u = out["gated"].reshape(shape + (len(UNITS),))
        wakes_u = out["wakes"].reshape(shape + (len(UNITS),))
        n_events = pa.lengths[stream_of_row].reshape(shape)

        gated = {c: gated_u[..., ui].astype(np.float64)
                 for ui, c in enumerate(COMPS)}
        wakes = {c: wakes_u[..., ui].astype(np.float64)
                 for ui, c in enumerate(COMPS)}
        setpm_isa = {"vu": pa.setpm_vu[stream_of_row].reshape(shape).copy(),
                     "sram": np.zeros(shape)}
        gated["sram"] = np.zeros(shape)

        # closed-form folds, once per unique (workload, npu, triple) —
        # identical host calls to execute_program's, so bit-identical; the
        # SRAM band analysis is window-independent, so it further dedups to
        # one call per (program, delay_scale)
        with bk.span("regate.folds"):
            pol_vu = _component_policies("ReGate-Full")["vu"]
            sram_memo: dict[tuple[int, float], dict] = {}
            for wi, wl in enumerate(workloads):
                for ai, npu in enumerate(npu_specs):
                    for ti, (saw, dsc, wsc) in enumerate(triples):
                        npu_eff = with_sa_width(npu, saw)
                        prog = lower_workload(wl, npu_eff)
                        kn = PolicyKnobs(delay_scale=dsc, window_scale=wsc,
                                         sa_width=saw)
                        fv = _fine_grained_vu_vec(
                            prog.tm, prog.tr, npu_eff, pol_vu, 1.0,
                            npu_eff.gating.leak_off_logic, kn)
                        gated["vu"][wi, ai, ti] = (
                            gated["vu"][wi, ai, ti]
                            + fv["gated_s"] * npu_eff.freq_hz)
                        setpm_isa["vu"][wi, ai, ti] += fv["setpm"]
                        wakes["vu"][wi, ai, ti] += fv["wakes"]
                        skey = (id(prog), float(dsc))
                        sb = sram_memo.get(skey)
                        if sb is None:
                            sb = sram_band_gating(prog, delay_scale=dsc)
                            sram_memo[skey] = sb
                        gated["sram"][wi, ai, ti] = (
                            sb["gated_segcycles"] / max(1, sb["n_segments"]))
                        setpm_isa["sram"][wi, ai, ti] = sb["setpm"]

        # the policy columns ride the same backend; the mesh is applied to
        # the kernel only (its row axis pads to divide the mesh — the
        # closed-form engine's op axis has no such padding and resolves its
        # own session mesh like every other sweep entry point)
        policy = evaluate_batch(workloads, npu_specs, ("ReGate-Full",),
                                grid, backend=backend)
        return ProgramPlaneBatch(
            workloads=tuple(wl.name for wl in workloads),
            npus=tuple(npu_specs), knob_grid=grid, triples=triples,
            inv=inv, cycles=cycles, stall_cycles=stalls, n_events=n_events,
            gated_cycles=gated, wake_events=wakes, setpm_isa=setpm_isa,
            policy=policy)
