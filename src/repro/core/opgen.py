"""Operator-trace generator — the simulator frontend (paper §4.4).

Lowers a workload description (the paper's Table 1 suite, or one of our
assigned architecture configs x input shapes) into a per-operator trace:
SA/VU FLOPs, HBM/ICI bytes, SRAM tile demand, and matmul dims for the SA
spatial-gating model. The backend (``repro.core.policies``) turns the trace
into per-component times and energies under each power-gating design.

The same role as the paper artifact's ``llm_ops_generator``.
"""
from __future__ import annotations

import math
import weakref
from dataclasses import dataclass, field, replace
from typing import Optional

import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.backend import host_span


@dataclass(frozen=True)
class Op:
    name: str
    flops_sa: float = 0.0          # MXU-mapped FLOPs
    flops_vu: float = 0.0          # vector FLOPs
    bytes_hbm: float = 0.0
    bytes_ici: float = 0.0
    sram_demand: int = 0           # resident bytes needed (tile working set)
    matmul_dims: Optional[tuple[int, int, int]] = None  # (M, K, N) per SA op
    count: int = 1                 # consecutive repetitions (e.g. layers)
    collective: bool = False       # uses ICI

    def scaled(self, n: int) -> "Op":
        return replace(self, count=self.count * n)


@dataclass(frozen=True)
class Workload:
    name: str
    kind: str                      # train | prefill | decode
    ops: tuple[Op, ...]
    n_chips: int = 1
    note: str = ""

    def total(self, attr: str) -> float:
        return sum(getattr(o, attr) * o.count for o in self.ops)


# --------------------------------------------------------------------------
# Columnar trace compilation (struct-of-arrays backend representation)
# --------------------------------------------------------------------------

@dataclass(frozen=True, eq=False)
class TraceArrays:
    """Struct-of-arrays view of a Workload's op stream.

    One entry per Op (NOT per executed instance — ``count`` carries the
    repetition factor, matching the scalar engine's per-op accounting).
    ``matmul_dims`` is split into ``mm_m/mm_k/mm_n`` with ``has_mm``
    masking the rows where it was None (sentinel dims are 1).

    The ``_derived`` dict caches per-NPU service-time arrays computed by
    the policy engine; it is keyed by quantities that do not depend on
    gating knobs, so one compiled trace serves every (policy, knobs) cell
    of a sweep.
    """

    n_ops: int
    flops_sa: np.ndarray       # f8 (n_ops,)
    flops_vu: np.ndarray       # f8
    bytes_hbm: np.ndarray      # f8
    bytes_ici: np.ndarray      # f8
    sram_demand: np.ndarray    # f8
    count: np.ndarray          # f8 — repetitions per op
    collective: np.ndarray     # bool
    has_mm: np.ndarray         # bool
    mm_m: np.ndarray           # i8 (1 where has_mm is False)
    mm_k: np.ndarray           # i8
    mm_n: np.ndarray           # i8
    names: tuple[str, ...]
    _derived: dict = field(default_factory=dict, compare=False, repr=False)

    @property
    def n_instances(self) -> float:
        """Executed op-stream length (counts expanded)."""
        return float(self.count.sum())

    def total(self, attr: str) -> float:
        return float((getattr(self, attr) * self.count).sum())


# Identity-keyed: hashing a Workload walks its full op tuple (~11k frozen
# dataclasses for the paper suite), which costs more than the vectorized
# evaluation itself. Weak refs keep the cache from pinning workloads
# alive; the finalizer drops an entry when its workload is collected, so
# ids can never be observed after reuse.
_TRACE_CACHE: dict[int, tuple["weakref.ref", "TraceArrays"]] = {}


def compile_trace(wl: Workload) -> TraceArrays:
    """Lower a Workload's op tuple into cached columnar arrays."""
    hit = _TRACE_CACHE.get(id(wl))
    if hit is not None and hit[0]() is wl:
        return hit[1]
    tr = _compile_trace(wl)
    key = id(wl)
    _TRACE_CACHE[key] = (weakref.ref(wl, lambda _: _TRACE_CACHE.pop(key,
                                                                    None)),
                         tr)
    return tr


def _validate_trace(wl: Workload, cols: dict[str, np.ndarray],
                    has_mm: np.ndarray, dims: np.ndarray) -> None:
    """Reject malformed op streams before they reach the policy engine.

    Negative or non-finite service-time carriers (flops / bytes /
    counts) would silently corrupt durations, idle gaps, and energy
    totals downstream — raise a ``ValueError`` naming the workload, op,
    and field instead. Zero-dim matmuls are equally rejected (the SA
    occupancy model divides by them).
    """
    for fld, a in cols.items():
        bad = ~np.isfinite(a)
        kind = "non-finite"
        if not bad.any():
            bad = a < 0
            kind = "negative"
        if bad.any():
            i = int(np.flatnonzero(bad)[0])
            raise ValueError(
                f"workload {wl.name!r}: {kind} {fld}={a[i]!r} at op "
                f"{i} ({wl.ops[i].name!r}) — would corrupt service "
                f"times/energy silently")
    if has_mm.any() and (dims[has_mm] < 1).any():
        i = int(np.flatnonzero(has_mm & (dims < 1).any(axis=1))[0])
        raise ValueError(
            f"workload {wl.name!r}: matmul_dims must be >= 1, got "
            f"{wl.ops[i].matmul_dims} at op {i} ({wl.ops[i].name!r})")


def _compile_trace(wl: Workload) -> TraceArrays:
    ops = wl.ops
    n = len(ops)
    mm = [o.matmul_dims for o in ops]
    has_mm = np.array([d is not None for d in mm], bool)
    dims = np.array([d if d is not None else (1, 1, 1) for d in mm],
                    np.int64).reshape(n, 3) if n else np.zeros((0, 3),
                                                               np.int64)
    cols = {
        "flops_sa": np.array([o.flops_sa for o in ops], np.float64),
        "flops_vu": np.array([o.flops_vu for o in ops], np.float64),
        "bytes_hbm": np.array([o.bytes_hbm for o in ops], np.float64),
        "bytes_ici": np.array([o.bytes_ici for o in ops], np.float64),
        "sram_demand": np.array([o.sram_demand for o in ops],
                                np.float64),
        "count": np.array([o.count for o in ops], np.float64),
    }
    _validate_trace(wl, cols, has_mm, dims)
    return TraceArrays(
        n_ops=n,
        flops_sa=cols["flops_sa"],
        flops_vu=cols["flops_vu"],
        bytes_hbm=cols["bytes_hbm"],
        bytes_ici=cols["bytes_ici"],
        sram_demand=cols["sram_demand"],
        count=cols["count"],
        collective=np.array([o.collective for o in ops], bool),
        has_mm=has_mm,
        mm_m=dims[:, 0], mm_k=dims[:, 1], mm_n=dims[:, 2],
        names=tuple(o.name for o in ops),
    )


# --------------------------------------------------------------------------
# Ragged trace stacking (the batched sweep plane's super-trace)
# --------------------------------------------------------------------------

@dataclass(frozen=True, eq=False)
class StackedTrace:
    """Ragged stack of per-workload ``TraceArrays``: one concatenated op
    stream plus segment bookkeeping.

    ``offsets`` (W+1,) holds the op-range of workload ``w`` as
    ``[offsets[w], offsets[w+1])``; ``seg_ids`` (N,) maps each op back to
    its workload. The batched policy engine
    (``repro.core.policies.evaluate_batch``) runs its array passes over
    the full stack and recovers per-workload results with segmented
    reductions, so gap merging and every other cross-op accumulation is
    bounded by the segment — idle intervals never leak across workload
    boundaries.

    ``_derived`` caches per-NPU stacked service times and idle-gap
    structures (keyed by spec identity, same convention as
    ``TraceArrays._derived``).
    """

    traces: tuple[TraceArrays, ...]
    names: tuple[str, ...]         # workload names, one per segment
    n_ops: int
    offsets: np.ndarray            # i8 (W+1,) op-range starts, last = n_ops
    seg_ids: np.ndarray            # i8 (N,) workload index per op
    flops_sa: np.ndarray           # f8 (N,) concatenated columns
    flops_vu: np.ndarray
    bytes_hbm: np.ndarray
    bytes_ici: np.ndarray
    sram_demand: np.ndarray
    count: np.ndarray
    collective: np.ndarray         # bool
    has_mm: np.ndarray             # bool
    _derived: dict = field(default_factory=dict, compare=False, repr=False)

    @property
    def n_segments(self) -> int:
        return len(self.names)


# Keyed by the tuple of compiled-trace ids. The cached StackedTrace holds
# strong references to its traces, so the ids stay valid for exactly as
# long as the entry exists; a small FIFO bound keeps ad-hoc sweeps from
# growing the cache without limit.
_STACK_CACHE: dict[tuple[int, ...], "StackedTrace"] = {}
_STACK_CACHE_MAX = 64


def stack_traces(workloads) -> StackedTrace:
    """Stack the compiled traces of ``workloads`` into one super-trace.

    Accepts a single Workload or a sequence; results are cached by the
    identity tuple of the compiled traces (compilation itself is cached
    per workload), so repeated sweeps over the same suite stack once.
    """
    if isinstance(workloads, Workload):
        workloads = [workloads]
    workloads = list(workloads)
    for i, wl in enumerate(workloads):
        if not isinstance(wl, Workload):
            raise ValueError(
                f"stack_traces expects Workload instances, got "
                f"{type(wl).__name__} at index {i}")
    # compile_trace validates each op stream (negative / non-finite
    # carriers raise), so a malformed trace can never enter the stack
    traces = tuple(compile_trace(wl) for wl in workloads)
    # a key hit implies identity: the entry holds strong refs to exactly
    # the traces whose ids form its key, so those ids cannot be reused
    key = tuple(id(tr) for tr in traces)
    hit = _STACK_CACHE.get(key)
    if hit is not None:
        return hit
    lengths = np.array([tr.n_ops for tr in traces], np.int64)
    offsets = np.zeros(len(traces) + 1, np.int64)
    np.cumsum(lengths, out=offsets[1:])
    n = int(offsets[-1])
    seg_ids = np.repeat(np.arange(len(traces), dtype=np.int64), lengths)

    def cat(attr, dtype):
        if not traces:
            return np.zeros(0, dtype)
        return np.concatenate([getattr(tr, attr) for tr in traces])

    st = StackedTrace(
        traces=traces, names=tuple(wl.name for wl in workloads),
        n_ops=n, offsets=offsets, seg_ids=seg_ids,
        flops_sa=cat("flops_sa", np.float64),
        flops_vu=cat("flops_vu", np.float64),
        bytes_hbm=cat("bytes_hbm", np.float64),
        bytes_ici=cat("bytes_ici", np.float64),
        sram_demand=cat("sram_demand", np.float64),
        count=cat("count", np.float64),
        collective=cat("collective", bool),
        has_mm=cat("has_mm", bool),
    )
    if len(_STACK_CACHE) >= _STACK_CACHE_MAX:
        _STACK_CACHE.pop(next(iter(_STACK_CACHE)))
    _STACK_CACHE[key] = st
    return st


def segmented_gaps(active: np.ndarray, idle: np.ndarray,
                   offsets: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Merged idle-gap lengths per segment — the intervals the scalar
    oracle's ``close_gap`` sees, for every workload of a stack.

    ``active``/``idle`` are per-op over the whole stack (idle holds
    dur*count where the component is inactive, 0 where active). Each
    segment contributes one gap per active op (the merged idle time since
    the previous active op *within the segment*) plus one trailing gap;
    segment boundaries always break a gap, so idle time never merges
    across workloads. Returns ``(gap_vals, gap_offsets)`` where
    ``gap_offsets`` (W+1,) slices ``gap_vals`` per segment.

    Empty (zero-op) segments own zero gaps — their slice of
    ``gap_vals`` is empty and neighbouring segments keep their own
    trailing/leading gaps, so a zero-op workload in a stack contributes
    exactly nothing. (``repro.core.backend.gap_index`` is the
    fixed-shape counterpart used under ``jit``.)
    """
    offsets = np.asarray(offsets, np.int64)
    n_seg = len(offsets) - 1
    idx = np.flatnonzero(active)
    # a bound both ends the previous gap and starts the next one; segment
    # starts are always bounds, so chunks never span two workloads
    bounds = np.union1d(offsets[:-1], idx + 1)
    idle2 = np.append(idle, 0.0)
    if bounds.size == 0:
        return np.zeros(0), np.zeros(n_seg + 1, np.int64)
    gap_vals = np.add.reduceat(idle2, bounds)
    # chunk ownership: the segment containing the chunk's starting bound
    gseg = np.minimum(np.searchsorted(offsets, bounds, side="right") - 1,
                      n_seg - 1)
    gap_offsets = np.searchsorted(gseg, np.arange(n_seg + 1))
    return gap_vals, gap_offsets


# --------------------------------------------------------------------------
# Helpers
# --------------------------------------------------------------------------

BF16 = 2
F32 = 4


def _matmul(name, M, K, N, *, bytes_w=BF16, bytes_act=BF16, n_chips=1,
            count=1, sram_tile=None, reread=1.0) -> Op:
    """A [M,K]x[K,N] matmul; weights + activations stream from HBM.

    SRAM demand follows the paper's Fig 7 methodology: the minimum tile
    size that maximizes on-chip reuse. Compute-bound shapes (large M) want
    the weight tile resident plus double-buffered activations; memory-bound
    shapes (small M — decode GEMVs) gain nothing from large tiles and only
    need enough to hide HBM latency.
    """
    flops = 2.0 * M * K * N
    b = (K * N * bytes_w + M * K * bytes_act * reread + M * N * bytes_act)
    if sram_tile is None:
        if M >= 512:  # compute-bound: weight-stationary large tiles
            sram_tile = min(int(0.75 * 128 * 2 ** 20),
                            K * N * bytes_w + 2 * 512 * K * bytes_act
                            + 512 * N * F32)
        else:  # streaming: latency-hiding double buffers only
            sram_tile = min(8 << 20, b)
    # VU post-processes SA outputs (accumulate/cast/activation): fine-
    # grained interleaved work, 1 VU-op per output element (paper Fig 15)
    return Op(name, flops_sa=flops / n_chips,
              flops_vu=M * N * 2.0 / n_chips,
              bytes_hbm=b / n_chips,
              sram_demand=int(sram_tile), matmul_dims=(M, K, N),
              count=count)


def _vector(name, elems, flops_per_elem=2.0, bytes_per_elem=2 * BF16,
            n_chips=1, count=1, sram_tile=4 << 20) -> Op:
    return Op(name, flops_vu=elems * flops_per_elem / n_chips,
              bytes_hbm=elems * bytes_per_elem / n_chips,
              sram_demand=sram_tile, count=count)


def _collective(name, bytes_per_chip, count=1, sram_tile=8 << 20) -> Op:
    return Op(name, bytes_ici=bytes_per_chip, count=count,
              sram_demand=sram_tile, collective=True)


# --------------------------------------------------------------------------
# Paper Table 1 workloads (LLM train/prefill/decode, DLRM, diffusion)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class LLMCfg:
    name: str
    L: int
    d: int
    H: int
    Hkv: int
    ff: int
    vocab: int


LLAMA = {
    "llama3-8b": LLMCfg("llama3-8b", 32, 4096, 32, 8, 14336, 128256),
    "llama2-13b": LLMCfg("llama2-13b", 40, 5120, 40, 40, 13824, 32000),
    "llama3-70b": LLMCfg("llama3-70b", 80, 8192, 64, 8, 28672, 128256),
    "llama3.1-405b": LLMCfg("llama3.1-405b", 126, 16384, 128, 8, 53248,
                            128256),
}


def llm_layer_ops(c: LLMCfg, T: int, *, n_chips: int, kv_len: int,
                  decode: bool, tp: int) -> list[Op]:
    """One transformer layer processing T tokens (per-chip amounts).

    tp: tensor-parallel ways (weights divided; activations all-reduced).
    """
    hd = c.d // c.H
    ops: list[Op] = []
    kv_dim = c.Hkv * hd
    # qkv + out projections (weights sharded tp-ways)
    ops.append(_matmul("qkv_proj", T, c.d, (c.d + 2 * kv_dim) // tp))
    if decode:
        # attention against KV cache: small M -> mapped to VU when tiny
        att_flops = 2.0 * T * kv_len * hd * c.H / tp * 2
        ops.append(Op("attn_decode", flops_vu=att_flops,
                      bytes_hbm=kv_len * kv_dim * BF16 * 2 / tp * max(1, T // 8),
                      sram_demand=8 << 20))
    else:
        # flash attention, scores+av on the SA
        att = 2.0 * T * kv_len * hd * 2 * (c.H / tp)
        ops.append(Op("attention", flops_sa=att,
                      bytes_hbm=3 * T * c.d * BF16 / tp,
                      matmul_dims=(T, hd, kv_len), sram_demand=24 << 20))
    ops.append(_matmul("out_proj", T, c.d // tp, c.d))
    ops.append(_collective("ar_attn", 2 * T * c.d * BF16 * (tp - 1) / tp)
               if tp > 1 else _vector("residual1", T * c.d))
    ops.append(_vector("rmsnorm1", T * c.d, flops_per_elem=4))
    ops.append(_matmul("mlp_up", T, c.d, 2 * c.ff // tp))
    ops.append(_vector("swiglu", T * c.ff / tp, flops_per_elem=3,
                       bytes_per_elem=0.5))
    ops.append(_matmul("mlp_down", T, c.ff // tp, c.d))
    ops.append(_collective("ar_mlp", 2 * T * c.d * BF16 * (tp - 1) / tp)
               if tp > 1 else _vector("residual2", T * c.d))
    ops.append(_vector("rmsnorm2", T * c.d, flops_per_elem=4))
    return ops


def llm_workload(model: str, phase: str, *, batch: int, seq: int = 4096,
                 out_seq: int = 512, n_chips: int = 1, tp: int = 1,
                 dp: int = 1) -> Workload:
    c = LLAMA[model]
    ops: list[Op] = []
    if phase == "train":
        T = batch * seq // dp
        layer = llm_layer_ops(c, T, n_chips=n_chips, kv_len=seq,
                              decode=False, tp=tp)
        # fwd + bwd (2x matmuls in bwd), layer sequences interleaved so the
        # per-component idle-gap structure matches real execution order
        ops += list(layer) * c.L
        bwd = [replace(o, name=o.name + "_bwd",
                       flops_sa=o.flops_sa * 2, flops_vu=o.flops_vu * 2,
                       bytes_hbm=o.bytes_hbm * 2) for o in layer]
        ops += list(bwd) * c.L
        ops.append(_matmul("lm_head", T, c.d, c.vocab // tp))
        n_params = c.L * (c.d * (c.d + 2 * c.Hkv * (c.d // c.H))
                          + c.d * c.d + 3 * c.d * c.ff) + c.d * c.vocab
        ops.append(_collective("grad_allreduce",
                               2 * n_params / (tp * dp) * BF16))
        ops.append(_vector("adam_update", n_params / (tp * dp),
                           flops_per_elem=12, bytes_per_elem=16))
    elif phase == "prefill":
        T = batch * seq
        layer = llm_layer_ops(c, T, n_chips=n_chips, kv_len=seq,
                              decode=False, tp=tp)
        ops += list(layer) * c.L
        ops.append(_matmul("lm_head", batch, c.d, c.vocab // tp))
    else:  # decode
        T = batch
        layer = llm_layer_ops(c, T, n_chips=n_chips, kv_len=seq + out_seq // 2,
                              decode=True, tp=tp)
        ops += list(layer) * c.L
        ops.append(_matmul("lm_head", T, c.d, c.vocab // tp))
    return Workload(f"{model}-{phase}", phase, tuple(ops), n_chips=n_chips)


def dlrm_workload(size: str, *, batch: int = 1024, n_chips: int = 8) \
        -> Workload:
    """DLRM: embedding-gather bound + small MLPs (paper: S/M/L tables)."""
    table_gb = {"S": 20, "M": 45, "L": 98}[size]
    n_tables, emb_dim = 64, 128
    lookups = 80
    bottom = [512, 256, 128]
    top = [1024, 1024, 512, 256, 1]
    ops: list[Op] = []
    # embedding gathers: HBM-random-access bound, tiny SRAM demand
    gather_bytes = batch * n_tables * lookups * emb_dim * F32 / n_chips
    ops.append(Op("emb_gather", bytes_hbm=gather_bytes,
                  flops_vu=batch * n_tables * lookups * emb_dim / n_chips,
                  sram_demand=4 << 20))
    # all-to-all to exchange embedding shards (model-parallel tables)
    ops.append(_collective("emb_alltoall",
                           batch * n_tables * emb_dim * F32 / n_chips,
                           sram_tile=4 << 20))
    prev = 13
    for i, w in enumerate(bottom):
        ops.append(_matmul(f"bot_mlp{i}", batch, prev, w, sram_tile=2 << 20))
        prev = w
    inter = n_tables + 1
    ops.append(_vector("interaction", batch * inter * inter * emb_dim / 64,
                       sram_tile=2 << 20))
    prev = inter * (inter - 1) // 2 + 128
    for i, w in enumerate(top):
        ops.append(_matmul(f"top_mlp{i}", batch, prev, w, sram_tile=2 << 20))
        prev = w
    return Workload(f"dlrm-{size}", "decode", tuple(ops), n_chips=n_chips,
                    note=f"tables={table_gb}GB")


def diffusion_workload(model: str, *, batch: int = 8, n_chips: int = 4) \
        -> Workload:
    ops: list[Op] = []
    if model == "dit-xl":
        L, d, H, ff, T = 28, 1152, 16, 4608, 1024
        hd = 72  # paper: head size 72 < SA width 128 -> spatial underuse
        steps = 4  # denoising steps folded into op counts
        Tb = T * batch
        for _ in range(1):
            layer = [
                _matmul("qkv", Tb, d, 3 * d),
                Op("attention", flops_sa=2.0 * Tb * T * hd * 2 * H,
                   bytes_hbm=3 * Tb * d * BF16,
                   matmul_dims=(Tb, hd, T), sram_demand=16 << 20),
                _matmul("proj", Tb, d, d),
                _vector("adaln", Tb * d, flops_per_elem=6),
                _matmul("mlp1", Tb, d, ff),
                _vector("gelu", Tb * ff, flops_per_elem=4, bytes_per_elem=0),
                _matmul("mlp2", Tb, ff, d),
            ]
            ops += [o.scaled(L * steps) for o in layer]
    else:  # gligen (U-Net): conv stages with shrinking spatial dims
        steps = 4
        res, ch = 64, 320
        for stage in range(4):
            r = res >> stage
            c_in = ch * (2 ** min(stage, 2))
            T = r * r * batch
            # conv as implicit GEMM: M=T, K=9*c_in, N=c_out
            ops.append(_matmul(f"conv{stage}", T, 9 * c_in, c_in,
                               count=6 * steps))
            if stage >= 1:  # attention blocks at lower res; head dim shrinks
                hd = max(40, 160 >> stage)
                ops.append(Op(f"attn{stage}",
                              flops_sa=2.0 * T * T / batch * hd * 2 * 8,
                              bytes_hbm=3 * T * c_in * BF16,
                              matmul_dims=(T, hd, T // batch),
                              sram_demand=16 << 20, count=2 * steps))
            ops.append(_vector(f"groupnorm{stage}", T * c_in,
                               flops_per_elem=6, count=6 * steps))
    return Workload(model, "prefill", tuple(ops), n_chips=n_chips)


# --------------------------------------------------------------------------
# Assigned-architecture workloads (execution plane -> power plane bridge)
# --------------------------------------------------------------------------

def _full_terms(cfg: ArchConfig) -> bool:
    """Whether ``cfg``'s attention and Mamba-2 mixers carry three terms
    that the dense and SSM stacks leave out: every sequence's KV cache
    read in decode (not ``max(1, T // 8)`` of them), each decode token's
    SSM state read and written, and the B, C and dt columns of the SSM
    input projection and conv. True for a model traced from its
    ``layer_pattern``. A temporary compatibility switch: the benchmark's
    references of the dense and SSM configurations pin the shorter
    terms, and the switch goes when the terms are corrected together
    with those references (ROADMAP.md section 3)."""
    return bool(cfg.layer_pattern)


def _attention_ops(cfg: ArchConfig, T: int, kv_len: int, decode: bool,
                   tp: int) -> list[Op]:
    """Grouped-query attention: fused qkv projection, the attention
    itself (prefill on the SA, decode on the VU against the KV cache),
    and the output projection; heads split ``tp`` ways. Decode reads
    the KV cache of every one of the ``T`` sequences, or of
    ``max(1, T // 8)`` of them without ``_full_terms``."""
    D = cfg.d_model
    H = max(1, cfg.n_heads)
    hd = max(1, cfg.head_dim)
    ops = [_matmul("qkv", T, D, (H + 2 * cfg.n_kv_heads) * hd // tp)]
    if decode:
        seqs = T if _full_terms(cfg) else max(1, T // 8)
        ops.append(Op(
            "attn_decode",
            flops_vu=2.0 * T * kv_len * hd * 2 * H / tp,
            bytes_hbm=kv_len * cfg.n_kv_heads * hd * BF16 * 2 * seqs / tp,
            sram_demand=8 << 20))
    else:
        ops.append(Op(
            "attention", flops_sa=2.0 * T * kv_len * hd * 2 * H / tp,
            bytes_hbm=3 * T * D * BF16 / tp,
            matmul_dims=(T, hd, kv_len), sram_demand=24 << 20))
    ops.append(_matmul("out_proj", T, H * hd // tp, D))
    return ops


def _ssm_ops(cfg: ArchConfig, T: int, decode: bool, tp: int) -> list[Op]:
    """A Mamba-2 SSD mixer (arXiv:2405.21060) for ``T`` tokens, heads
    split ``tp`` ways: the input projection, the causal conv and its
    activation, the chunked scan (decode: one state update per token on
    the VU) and the output projection.

    With ``_full_terms`` the published layout is traced whole: the
    projection also emits the B and C groups and one dt a head, the conv
    runs over x, B and C, and each decode token reads and writes its
    sequence's (heads, head_dim, d_state) state. Without it the block
    keeps the x and z columns alone and no state traffic."""
    ss, D = cfg.ssm, cfg.d_model
    di, nh = ss.d_inner(D), ss.heads(D)
    proj, conv = 2 * di, di
    ssd_bytes = T * di * BF16 * 3 / tp
    if _full_terms(cfg):
        bc = 2 * ss.n_groups * ss.d_state
        proj, conv = 2 * di + bc + nh, di + bc
        if decode:
            ssd_bytes += T * nh * ss.head_dim * ss.d_state * BF16 * 2 / tp
    return [
        _matmul("in_proj", T, D, proj // tp),
        _vector("conv+act", T * conv / tp, flops_per_elem=10),
        Op("ssd", flops_vu=T * nh * ss.head_dim * ss.d_state * 6 / tp,
           flops_sa=(0 if decode else
                     2.0 * T * ss.chunk * ss.head_dim * nh * 2 / tp),
           bytes_hbm=ssd_bytes,
           matmul_dims=None if decode else (T, ss.head_dim, ss.chunk),
           sram_demand=16 << 20),
        _matmul("out_proj", T, di // tp, D),
    ]


def _mla_ops(cfg: ArchConfig, T: int, kv_len: int, decode: bool,
             tp: int) -> list[Op]:
    """DeepSeek-V2 multi-head latent attention (arXiv:2405.04434 §2.1)
    for ``T`` tokens over this chip's ``n_heads / tp`` heads.

    Both low-rank down projections run whole on every chip. Prefill and
    train expand the latent into per-head keys and values (``kv_b``)
    and attend as ordinary heads. Decode absorbs ``kv_b`` into the query
    and the output, as the paper serves it: every head then reads the
    one shared latent cache of ``kv_lora + rope`` per position, one
    (H, kv_lora + rope) x (kv_lora + rope, kv_len) pass per sequence on
    the SA. The absorbed projections stream one (nope, kv_lora) or
    (kv_lora, v) block of ``kv_b`` per head."""
    m, D, H = cfg.mla, cfg.d_model, max(1, cfg.n_heads // tp)
    qk = m.nope_head_dim + m.rope_head_dim
    lat = m.kv_lora_rank + m.rope_head_dim
    ops = [_matmul("q_a", T, D, m.q_lora_rank),
           _matmul("kv_a", T, D, lat),
           _vector("mla_norm_rope",
                   T * (m.q_lora_rank + m.kv_lora_rank
                        + (H + 1) * m.rope_head_dim), flops_per_elem=4),
           _matmul("q_b", T, m.q_lora_rank, H * qk)]
    if decode:
        ops += [
            _matmul("q_absorb", T * H, m.nope_head_dim, m.kv_lora_rank,
                    bytes_w=BF16 * H),
            Op("mla_decode",
               flops_sa=2.0 * H * kv_len * lat
               + 2.0 * H * kv_len * m.kv_lora_rank,
               bytes_hbm=kv_len * lat * BF16,
               matmul_dims=(H, lat, kv_len), sram_demand=8 << 20, count=T),
            _matmul("o_absorb", T * H, m.kv_lora_rank, m.v_head_dim,
                    bytes_w=BF16 * H)]
    else:
        ops += [
            _matmul("kv_b", T, m.kv_lora_rank,
                    H * (m.nope_head_dim + m.v_head_dim)),
            Op("attention",
               flops_sa=2.0 * T * kv_len * H * (qk + m.v_head_dim),
               bytes_hbm=T * H * (2 * qk + m.v_head_dim) * BF16,
               matmul_dims=(T, qk, kv_len), sram_demand=24 << 20)]
    ops.append(_matmul("o_proj", T, H * m.v_head_dim, D))
    return ops


def _expert_mlp(prefix: str, rows: int, D: int, F: int, act: str, *,
                split: int = 1, count: int = 1) -> list[Op]:
    """One expert MLP of width ``F``, split ``split`` ways, at M =
    ``rows``: the up GEMM (gate and up, D -> 2F, for SwiGLU; D -> F for
    the non-gated relu² of Nemotron-H), the activation on the VU, fused
    with the GEMM's output, and the down GEMM."""
    if act == "swiglu":
        up, flops = 2 * F // split, 3
    elif act == "relu2":
        up, flops = F // split, 2
    else:
        raise ValueError(f"unknown expert activation {act!r}; have "
                         f"'swiglu', 'relu2'")
    return [_matmul(f"{prefix}_up", rows, D, up, count=count),
            _vector(f"{prefix}_{act}", rows * F / split,
                    flops_per_elem=flops, bytes_per_elem=0.5, count=count),
            _matmul(f"{prefix}_down", rows, F // split, D, count=count)]


def _routed_experts(R: int, E: int, D: int, F: int,
                    act: str = "swiglu") -> list[Op]:
    """The ``E`` routed experts a chip holds, fed ``R`` token slots
    spread as evenly as integers allow over ``min(E, R)`` of them. Each
    expert's up GEMM, activation and down GEMM is priced at its own M;
    the experts that share an M are one op's ``count``, so there are at
    most two ops per GEMM, and every active expert's weights stream
    once."""
    n = min(E, R)
    m, extra = divmod(R, n)
    ops: list[Op] = []
    for rows, k in ((m + 1, extra), (m, n - extra)):
        if k:
            ops += _expert_mlp("expert", rows, D, F, act, count=k)
    return ops


def _deepseek_moe_ops(mo, T: int, D: int, *, n_chips: int,
                      tp: int) -> list[Op]:
    """DeepSeekMoE (arXiv:2405.04434 §2.2), expert parallel: the routed
    experts are spread over ``ep = gcd(n_experts, n_chips)`` chips, and
    this one holds ``n_experts / ep`` of them. Its ``T // tp`` tokens
    are routed over all experts, sent once per chosen expert to the
    expert's chip and combined back (all-to-all), with balanced routing
    and no token dropped. The shared experts run every token, split
    ``tp`` ways, at ``d_ff_shared`` where it is given (else
    ``n_shared_experts`` x ``d_ff_expert``); every expert MLP is gated
    (SwiGLU) or not (relu², Nemotron-H) as ``mo.act`` says."""
    ep = math.gcd(mo.n_experts, n_chips)
    T_m = max(1, T // tp)
    R = T_m * mo.top_k
    a2a = R * D * BF16 * (ep - 1) / ep
    ops = [_matmul("router", T_m, D, mo.n_experts),
           _vector("router_topk", T_m * mo.n_experts, flops_per_elem=4),
           _collective("a2a_dispatch", a2a)]
    ops += _routed_experts(R, mo.n_experts // ep, D, mo.d_ff_expert,
                           mo.act)
    ops.append(_collective("a2a_combine", a2a))
    if mo.n_shared_experts:
        fs = mo.d_ff_shared or mo.n_shared_experts * mo.d_ff_expert
        ops += _expert_mlp("shared", T, D, fs, mo.act, split=tp)
    return ops


# the op rows each kind of mixer emitted, as ``arch_workload``'s span counts
_MIXER_COUNT = {"M": "ssm_ops", "*": "attn_ops", "E": "moe_ops"}


def arch_workload(cfg: ArchConfig, shape: ShapeConfig, *, n_chips: int = 256,
                  tp: int = 16) -> Workload:
    """Analytic operator trace for one of our (arch x shape) cells.

    Used when HLO statistics are not available (and cross-checked against
    the dry-run numbers in the benchmarks). Attention is latent (MLA)
    where ``cfg.mla`` is set; with ``cfg.moe`` every layer after the
    leading dense ones runs expert-parallel DeepSeekMoE. A model with a
    ``layer_pattern`` is traced block by block: one mixer a block (``M``
    Mamba-2, ``E`` MoE, ``*`` attention), each followed by the layer
    tail.

    Opens the host span ``regate.arch_workload`` (``backend.host_span``)
    with the counts ``layers``, ``ops`` and the rows each kind of mixer
    emitted (``ssm_ops``, ``attn_ops``, ``moe_ops``; a tail, a dense MLP
    and the head count in ``ops`` alone).
    """
    with host_span("regate.arch_workload") as ann:
        wl, counts = _arch_workload(cfg, shape, n_chips=n_chips, tp=tp)
        if ann is not None:
            ann.set_metadata(layers=cfg.n_layers, ops=len(wl.ops), **counts)
    return wl


def _arch_workload(cfg: ArchConfig, shape: ShapeConfig, *, n_chips: int,
                   tp: int) -> tuple[Workload, dict]:
    ops: list[Op] = []
    counts = dict.fromkeys(_MIXER_COUNT.values(), 0)
    decode = shape.kind == "decode"
    B, S = shape.global_batch, shape.seq_len
    dp = max(1, n_chips // tp)
    T = (B if decode else B * S) // dp
    T = max(1, T)
    D = cfg.d_model
    kv_len = S
    train = shape.kind == "train"

    def add_layer(ops_layer, L):
        mult = 3 if train else 1  # fwd + 2x bwd
        seq_ops = [replace(o, flops_sa=o.flops_sa * mult,
                           flops_vu=o.flops_vu * mult,
                           bytes_hbm=o.bytes_hbm * mult)
                   for o in ops_layer]
        ops.extend(seq_ops * L)

    tail = []
    if tp > 1:
        tail.append(_collective("ar_layer",
                                2 * T * D * BF16 * (tp - 1) / tp))
    tail.append(_vector("norms", T * D, flops_per_elem=8))

    if cfg.layer_pattern:
        unknown = sorted(set(cfg.layer_pattern) - set(_MIXER_COUNT))
        if unknown:
            raise ValueError(f"{cfg.name}: layer_pattern letters {unknown} "
                             f"are none of M (Mamba-2), E (MoE), * "
                             f"(attention)")
        if len(cfg.layer_pattern) != cfg.n_layers:
            raise ValueError(f"{cfg.name}: layer_pattern has "
                             f"{len(cfg.layer_pattern)} blocks, n_layers "
                             f"{cfg.n_layers}")
        if train:
            raise ValueError(f"{cfg.name}: a train step of a layer_pattern "
                             f"model is not traced (no parameter count)")
        build = {"M": lambda: _ssm_ops(cfg, T, decode, tp),
                 "*": lambda: _attention_ops(cfg, T, kv_len, decode, tp),
                 "E": lambda: _deepseek_moe_ops(cfg.moe, T, D,
                                                n_chips=n_chips, tp=tp)}
        mixers: dict[str, list[Op]] = {}
        for letter in cfg.layer_pattern:
            if letter not in mixers:
                mixers[letter] = build[letter]()
            ops += mixers[letter] + tail   # no train: the ops as built
            counts[_MIXER_COUNT[letter]] += len(mixers[letter])
    elif cfg.family == "ssm":
        layer = _ssm_ops(cfg, T, decode, tp)
        add_layer(layer, cfg.n_layers)
        counts["ssm_ops"] = len(layer) * cfg.n_layers
    else:
        attend = _mla_ops if cfg.mla else _attention_ops
        attn = attend(cfg, T, kv_len, decode, tp)
        counts["attn_ops"] = len(attn) * cfg.n_layers
        mlp = []
        if cfg.d_ff:
            mlp = [_matmul("mlp_up", T, D, 2 * cfg.d_ff // tp),
                   _matmul("mlp_down", T, cfg.d_ff // tp, D)]
        if cfg.moe:
            # leading dense layers keep the dense MLP at d_ff
            lead = cfg.moe.first_dense_layers
            add_layer(attn + mlp + tail, lead)
            moe = _deepseek_moe_ops(cfg.moe, T, D, n_chips=n_chips, tp=tp)
            add_layer(attn + moe + tail, cfg.n_layers - lead)
            counts["moe_ops"] = len(moe) * (cfg.n_layers - lead)
        else:
            add_layer(attn + mlp + tail, cfg.n_layers)

    ops.append(_matmul("lm_head", T, D, cfg.vocab_padded // tp))
    if train:
        from repro.models.registry import count_params
        n_params = count_params(cfg)
        ops.append(_collective("grad_allreduce",
                               2 * n_params * BF16 / (tp * dp)))
        ops.append(_vector("adam", n_params / (tp * dp), flops_per_elem=12,
                           bytes_per_elem=16))
    return (Workload(f"{cfg.name}-{shape.name}", shape.kind, tuple(ops),
                     n_chips=n_chips), counts)


# --------------------------------------------------------------------------
# The paper's benchmark suite (Table 1 / Table 4 -like configs on NPU-D)
# --------------------------------------------------------------------------

def paper_suite() -> list[Workload]:
    """The suite workloads are immutable and identical across calls, so
    they are built once; repeated calls return the same Workload objects
    and therefore hit the compiled-trace cache."""
    return list(_paper_suite())


def _paper_suite() -> tuple[Workload, ...]:
    global _PAPER_SUITE
    if _PAPER_SUITE is None:
        _PAPER_SUITE = tuple(_build_paper_suite())
    return _PAPER_SUITE


_PAPER_SUITE: Optional[tuple[Workload, ...]] = None


def _build_paper_suite() -> list[Workload]:
    return [
        llm_workload("llama3-8b", "train", batch=32, n_chips=4, tp=4),
        llm_workload("llama2-13b", "train", batch=32, n_chips=4, tp=4),
        llm_workload("llama3-70b", "train", batch=32, n_chips=8, tp=8),
        llm_workload("llama3.1-405b", "train", batch=32, n_chips=16, tp=16),
        llm_workload("llama3-8b", "prefill", batch=4, n_chips=1),
        llm_workload("llama2-13b", "prefill", batch=4, n_chips=1),
        llm_workload("llama3-70b", "prefill", batch=8, n_chips=4, tp=4),
        llm_workload("llama3.1-405b", "prefill", batch=8, n_chips=8, tp=8),
        llm_workload("llama3-8b", "decode", batch=8, n_chips=1),
        llm_workload("llama2-13b", "decode", batch=4, n_chips=1),
        llm_workload("llama3-70b", "decode", batch=32, n_chips=4, tp=4),
        llm_workload("llama3.1-405b", "decode", batch=64, n_chips=8, tp=8),
        dlrm_workload("S"), dlrm_workload("M"), dlrm_workload("L"),
        diffusion_workload("dit-xl"), diffusion_workload("gligen"),
    ]
