"""Pluggable array backend for the batched sweep plane (ISSUE 4).

The policy engine (``repro.core.policies.evaluate_batch``, and
``evaluate`` as its one-cell call) is a handful of segmented array
passes over a stacked super-trace. This module abstracts the array
substrate those passes run on so the same backend-neutral kernel
executes either on

* **numpy** — the kernel eagerly on the host, always available; or
* **jax**   — one ``jax.jit``-compiled program (knob axis via ``vmap``,
  segmented reductions via ``jax.ops.segment_sum``), reused across NPU
  generations because every per-generation quantity enters as a traced
  array, never as a Python constant baked into the trace.

The contract each backend provides:

* ``xp``                    — the array namespace (``numpy`` /
  ``jax.numpy``);
* ``segment_sum(data, seg_ids, num_segments)`` — 1-D segmented sum with
  sorted segment ids (empty segments sum to zero);
* ``jit(fn, static_argnames)`` / ``vmap_knobs(fn, knobs)`` — compile and
  knob-axis-map hooks (identity / Python loop on numpy);
* ``scan(f, init, xs, length)`` — carry-only sequential loop over the
  leading axis of the ``xs`` pytree (``lax.scan`` on jax): the
  program-plane event kernel's spine (``repro.core.program_plane``);
* ``asarray`` / ``to_numpy`` / ``compute_scope()`` — transfer in/out and
  the dtype discipline scope (jax: float64 via x64);
* ``copy_to_host_async(x)`` / ``block(tree)`` — start an output's copy
  to the host without waiting (the sweep launches every NPU's kernel
  before its first pull), and wait for a result needed at once (no-ops
  on numpy);
* ``span(name, counts=None)`` — a context manager around one layer of
  the call path (``regate.*``): on jax a
  ``jax.profiler.TraceAnnotation``, so a running profiler records it on
  the device trace's clock, with the integers that the callable
  ``counts`` returns as event stats (called only while a profiler
  records); it costs about a microsecond when none runs. On numpy a
  no-op;
* ``sa_occupancy(...)`` — the in-program SA PE-occupancy pass: the
  backend-neutral closed form, traced on jax, so SA width rides the
  knob axis;
* ``psum`` / ``all_gather`` / ``pspec`` / ``shard_map_kernel`` — the
  collective surface the multi-device ``shard_map`` sweep program is
  built from (jax only; resolved through ``parallel.jax_compat``).

A put to the chip costs about the same whatever its size, so the
single-device kernels take their inputs as one flat array per dtype:
``put_slabs`` packs a host dict pytree and puts each slab once, and
``unpack_slabs`` rebuilds the dict inside the jitted program by static
slices, its ``slab_layout`` a static argument.

Ragged gap merging (``opgen.segmented_gaps``) is data-dependent-shape
and cannot run under ``jit``; ``gap_index`` builds the equivalent
fixed-shape structure on the host once per stack — each op is assigned
the id of the idle-gap chunk that owns it, so the gap *values* become a
plain ``segment_sum`` over per-op idle time and the per-knob threshold
masking stays shape-stable inside the compiled program.

The jax backend requires float64 (the ≤1e-9 record equivalence against
the scalar oracle is meaningless at f32): entry points run inside
``compute_scope()``, which enables x64 locally through
``parallel.jax_compat.enable_x64`` and raises a clear error if arrays
still come out narrower.
"""
from __future__ import annotations

import contextlib
import math
from typing import Callable, Optional

import numpy as np

from repro.core import session

_X64_HELP = (
    "the jax sweep backend requires float64 (x64), but arrays inside "
    "its compute scope are not float64. The scope switches x64 on with "
    "`jax.enable_x64(True)` (repro.parallel.jax_compat.enable_x64); "
    "make sure nothing inside it turns x64 off, or enable it for the "
    "whole process with JAX_ENABLE_X64=1."
)


def _tree_stack(items: list):
    """Stack a list of identically-structured dict/array pytrees along a
    new leading axis (the numpy stand-in for ``vmap`` output batching)."""
    first = items[0]
    if isinstance(first, dict):
        return {k: _tree_stack([it[k] for it in items]) for k in first}
    return np.stack(items, axis=0)


def transfer_counts(tree) -> dict:
    """``arrays`` and ``bytes`` of a dict pytree's leaves: the counts a
    ``regate.put`` or ``regate.harvest`` span carries. Device arrays
    report ``nbytes`` without a transfer; a Python scalar is read as
    the 0-d array it is put as (8 bytes under x64)."""
    if isinstance(tree, dict):
        n = b = 0
        for v in tree.values():
            c = transfer_counts(v)
            n, b = n + c["arrays"], b + c["bytes"]
        return {"arrays": n, "bytes": b}
    return {"arrays": 1, "bytes": int(tree.nbytes if hasattr(tree, "nbytes")
                                      else np.asarray(tree).nbytes)}


def slab_layout(tree) -> tuple:
    """Static layout of a host dict pytree packed one flat array (slab)
    per dtype: ``(slabs, leaves)``, ``slabs`` the ``(dtype, size)`` of
    each slab and ``leaves`` the ``(key path, dtype, shape, slab,
    offset)`` of each leaf, keys walked in sorted order. A leaf rides
    the slab of its own dtype, a bool leaf the int64 one as 0/1; a
    Python scalar is the 0-d array it converts to. The layout depends
    only on keys, dtypes and shapes, so it is hashable and serves as a
    static argument of a jitted program: new values, the same program."""
    sizes: dict[str, int] = {}
    leaves = []

    def walk(t, path):
        if isinstance(t, dict):
            for k in sorted(t):
                walk(t[k], path + (k,))
            return
        a = np.asarray(t)
        slab = "int64" if a.dtype == np.bool_ else a.dtype.name
        off = sizes.get(slab, 0)
        leaves.append((path, a.dtype.name, a.shape, slab, off))
        sizes[slab] = off + a.size

    walk(tree, ())
    return tuple(sizes.items()), tuple(leaves)


def slab_counts(layout) -> dict:
    """``transfer_counts`` of the slabs ``layout`` packs into."""
    return {"arrays": len(layout[0]),
            "bytes": sum(n * np.dtype(d).itemsize for d, n in layout[0])}


def pack_slabs(tree, layout) -> dict[str, np.ndarray]:
    """The leaves of ``tree`` copied into one flat array per dtype, at
    ``layout``'s offsets: ``{dtype: slab}``."""
    parts: dict[str, list] = {d: [] for d, _n in layout[0]}
    for path, _dtype, _shape, slab, _off in layout[1]:
        v = tree
        for k in path:
            v = v[k]
        parts[slab].append(np.ravel(np.asarray(v, slab)))
    return {d: np.concatenate(p) for d, p in parts.items()}


def unpack_slabs(slabs: dict, layout) -> dict:
    """``pack_slabs`` undone by static slices and reshapes (inside a
    jitted program, on traced slabs): the same dict pytree, every leaf
    with its dtype, shape and bits."""
    out: dict = {}
    for path, dtype, shape, slab, off in layout[1]:
        v = slabs[slab][off:off + math.prod(shape)].reshape(shape)
        if dtype == "bool":
            v = v != 0
        d = out
        for k in path[:-1]:
            d = d.setdefault(k, {})
        d[path[-1]] = v
    return out


def put_slabs(tree, bk) -> tuple[tuple, dict]:
    """One ``regate.put`` of a host dict pytree: packed into one slab
    per dtype on the host, each slab put once. Returns ``(layout,
    {dtype: device slab})``; ``unpack_slabs`` rebuilds the tree. The
    packing copy counts as transfer work, inside the span."""
    layout = slab_layout(tree)
    with bk.span("regate.put", lambda: slab_counts(layout)):
        return layout, {d: bk.asarray(s)
                        for d, s in pack_slabs(tree, layout).items()}


class NumpyBackend:
    """Eager numpy instantiation of the backend contract: the sweep
    kernel on the host (``evaluate``, and ``evaluate_batch`` by
    default). The oracle it is checked against is the scalar
    ``policies.evaluate_reference``."""

    name = "numpy"
    xp = np

    @staticmethod
    def sa_occupancy(mm_m, mm_k, mm_n, saw, weight_load_cycles=None):
        """Per-op SA PE-occupancy stats (closed form, ``sa_gating``)."""
        from repro.core.sa_gating import gating_stats_batch_xp
        return gating_stats_batch_xp(mm_m, mm_k, mm_n, saw,
                                     weight_load_cycles, xp=np)

    @staticmethod
    def asarray(x):
        a = np.asarray(x)
        if a.dtype == np.float32:
            a = a.astype(np.float64)
        return a

    @staticmethod
    def to_numpy(x) -> np.ndarray:
        return np.asarray(x)

    @staticmethod
    def span(name: str, counts=None):
        return contextlib.nullcontext()

    @staticmethod
    def segment_sum(data, seg_ids, num_segments: int):
        return np.bincount(seg_ids, weights=np.asarray(data, np.float64),
                           minlength=num_segments)[:num_segments]

    @staticmethod
    def jit(fn: Callable, static_argnames=()) -> Callable:
        return fn

    @staticmethod
    def vmap_knobs(fn: Callable, knobs: dict) -> dict:
        k = len(next(iter(knobs.values())))
        return _tree_stack([fn({key: v[i] for key, v in knobs.items()})
                            for i in range(k)])

    @staticmethod
    @contextlib.contextmanager
    def compute_scope():
        yield

    @staticmethod
    def block(tree):
        return tree

    @staticmethod
    def copy_to_host_async(x) -> None:
        """Nothing to start: the array is on the host already."""

    @staticmethod
    def scan(f, init, xs, length: int):
        """Sequential carry loop (the numpy stand-in for ``lax.scan``).

        ``f(carry, x) -> carry`` with ``x`` the per-step slice of the
        ``xs`` pytree along its leading axis; returns the final carry.
        The program-plane event kernel is a scan over the event axis
        with the (stream, unit) axes vectorized inside the carry."""
        carry = init
        for i in range(length):
            carry = f(carry, {k: v[i] for k, v in xs.items()})
        return carry


class JaxBackend:
    """``jax.numpy`` instantiation: jit + vmap + x64 compute scope.

    jax is imported lazily so ``repro.core`` keeps zero import-time jax
    dependence; constructing the backend on a machine without jax raises
    a clear error instead of poisoning module import.
    """

    name = "jax"

    def __init__(self):
        try:
            import jax
            import jax.numpy as jnp
        except ImportError as e:  # pragma: no cover - jax ships in CI
            raise RuntimeError(
                "the 'jax' sweep backend needs jax installed; use "
                "backend='numpy' or install jax") from e
        self._jax = jax
        self.xp = jnp

    # -- x64 discipline ------------------------------------------------
    def x64_enabled(self) -> bool:
        return bool(self._jax.config.jax_enable_x64)

    @contextlib.contextmanager
    def compute_scope(self):
        """All transfers, traces, and executions of the jax sweep path
        run inside this scope so arrays stay float64 end-to-end."""
        from repro.parallel import jax_compat
        with jax_compat.enable_x64():
            if not self.x64_enabled():
                raise RuntimeError(_X64_HELP)
            yield

    # -- array contract ------------------------------------------------
    def asarray(self, x):
        return self.xp.asarray(x)

    def to_numpy(self, x) -> np.ndarray:
        return np.asarray(x)

    def span(self, name: str, counts: Optional[Callable[[], dict]] = None):
        """A host span for the profiler; the integers ``counts()``
        returns become its event stats in the ``.xplane.pb``. They are
        counted only while a profiler records: walking a tree of device
        arrays costs more than the span itself."""
        ann = self._jax.profiler.TraceAnnotation
        if counts is not None and ann.is_enabled():
            return ann(name, **counts())
        return ann(name)

    def segment_sum(self, data, seg_ids, num_segments: int):
        import jax.ops
        return jax.ops.segment_sum(data, seg_ids,
                                   num_segments=num_segments,
                                   indices_are_sorted=True)

    def jit(self, fn: Callable, static_argnames=()) -> Callable:
        return self._jax.jit(fn, static_argnames=static_argnames)

    def vmap_knobs(self, fn: Callable, knobs: dict):
        return self._jax.vmap(fn)(knobs)

    def block(self, tree):
        """Wait for async dispatch where a result is needed at once (the
        event-scan kernel, whose outputs the host pulls right away)."""
        return self._jax.block_until_ready(tree)

    @staticmethod
    def copy_to_host_async(x) -> None:
        """Start the device-to-host copy of ``x`` without waiting, so
        that a later ``to_numpy`` finds it landed or under way: the
        sweep's harvest pulls one NPU's slab while the next computes."""
        x.copy_to_host_async()

    def scan(self, f, init, xs, length: int):
        """``lax.scan`` with a carry-only body (no stacked outputs): the
        jit'd form of the numpy backend's sequential loop, used by the
        program-plane event kernel."""
        carry, _ = self._jax.lax.scan(
            lambda c, x: (f(c, x), None), init, xs, length=length)
        return carry

    def sa_occupancy(self, mm_m, mm_k, mm_n, saw, weight_load_cycles=None):
        """Per-op SA PE-occupancy stats, computed *inside* the traced
        sweep program (``saw`` may be a traced scalar — the SA-width
        knob axis), through the backend-neutral closed form."""
        from repro.core.sa_gating import gating_stats_batch_xp
        return gating_stats_batch_xp(mm_m, mm_k, mm_n, saw,
                                     weight_load_cycles, xp=self.xp)

    # -- optional multi-device sharding --------------------------------
    def op_axis_sharding(self, mesh):
        """NamedSharding pair (shard-over-ops, replicated) for placing
        the stacked-trace data on a ``jax_compat`` mesh. The op axis is
        the workload axis of the stack (segments are spans of ops), so
        sharding it spreads the per-op work across devices while the
        (W,)-sized segmented outputs stay replicated."""
        from jax.sharding import NamedSharding, PartitionSpec
        return (NamedSharding(mesh, PartitionSpec("wl")),
                NamedSharding(mesh, PartitionSpec()))

    def shard_data(self, data: dict, mesh) -> dict:
        """Device-put a prepared data pytree: ``data["op"]`` leaves are
        sharded along the op axis, everything else replicated."""
        shard, repl = self.op_axis_sharding(mesh)
        jax = self._jax

        def put(tree, sh):
            if isinstance(tree, dict):
                return {k: put(v, sh) for k, v in tree.items()}
            return jax.device_put(tree, sh)

        return {k: put(v, shard if k == "op" else repl)
                for k, v in data.items()}

    # -- shard_map execution path (ISSUE 5) ----------------------------
    @staticmethod
    def mesh_axis_sizes(mesh) -> dict[str, int]:
        from repro.parallel import jax_compat
        return jax_compat.mesh_axis_sizes(mesh)

    @staticmethod
    def pspec(*names):
        """``PartitionSpec`` constructor exposed through the contract so
        the policy engine never imports jax directly."""
        from jax.sharding import PartitionSpec
        return PartitionSpec(*names)

    def psum(self, tree, axis_name: str):
        """Cross-device sum over a mesh axis (inside ``shard_map``)."""
        return self._jax.lax.psum(tree, axis_name)

    def all_gather(self, tree, axis_name: str):
        """Gather shards along leading axis (inside ``shard_map``)."""
        return self._jax.lax.all_gather(tree, axis_name, axis=0,
                                        tiled=True)

    def shard_map_kernel(self, body: Callable, mesh, in_specs,
                         out_specs) -> Callable:
        """Compile ``body`` as one SPMD program over ``mesh`` via
        ``jax_compat.shard_map`` (replication checks off: the kernel's
        psums make every unmentioned-axis output genuinely
        replicated)."""
        from repro.parallel import jax_compat
        return self._jax.jit(jax_compat.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs))


_BACKENDS: dict[str, object] = {}

BACKEND_NAMES = ("numpy", "jax")


def get_backend(name: Optional[str] = None):
    """Resolve a backend by name (``None`` → the session default).

    Instances are cached: the jax backend holds jitted-program caches
    that must survive across sweep calls for compile-once reuse.
    """
    if name is None:
        name = session.resolve("backend")
    bk = _BACKENDS.get(name)
    if bk is not None:
        return bk
    if name == "numpy":
        bk = NumpyBackend()
    elif name == "jax":
        bk = JaxBackend()
    else:
        raise KeyError(f"unknown array backend {name!r}; "
                       f"have {BACKEND_NAMES}")
    _BACKENDS[name] = bk
    return bk


def set_default_backend(name: str) -> str:
    """Set the process default (what ``backend=None`` resolves to);
    returns the previous default. Delegates to the root
    ``repro.core.session`` layer — an active ``SweepSession`` that pins
    ``backend`` shadows the new default until it exits. Prefer
    ``with SweepSession(backend=...)`` for scoped overrides."""
    if name not in BACKEND_NAMES:
        raise KeyError(f"unknown array backend {name!r}; "
                       f"have {BACKEND_NAMES}")
    return session.set_root(backend=name)["backend"]


def default_backend() -> str:
    """The effective session default backend name."""
    return session.resolve("backend")


def failover_rungs(name: Optional[str] = None, jax_mesh=None) \
        -> tuple[tuple[str, object], ...]:
    """The guard plane's backend-downgrade ladder for a requested
    (backend, mesh): each rung is ``(rung_name, mesh)``, ordered from
    the requested substrate down to the host's numpy kernel —
    ``jax-mesh`` → ``jax`` (single device) → ``numpy``. A numpy
    request has nowhere to fall, so its ladder is just itself.
    ``None`` resolves through the active session, mirroring
    ``get_backend``."""
    if name is None:
        name = session.resolve("backend")
    if name not in BACKEND_NAMES:
        raise KeyError(f"unknown array backend {name!r}; "
                       f"have {BACKEND_NAMES}")
    if name == "numpy":
        return (("numpy", None),)
    if jax_mesh is None:
        jax_mesh = session.resolve("jax_mesh")
    rungs: list[tuple[str, object]] = []
    if jax_mesh is not None:
        rungs.append(("jax-mesh", jax_mesh))
    rungs.append(("jax", None))
    rungs.append(("numpy", None))
    return tuple(rungs)


# --------------------------------------------------------------------------
# fixed-shape gap indexing (host-side; replaces data-dependent reduceat)
# --------------------------------------------------------------------------

def gap_index(active: np.ndarray, offsets: np.ndarray) \
        -> tuple[np.ndarray, np.ndarray]:
    """Fixed-shape equivalent of ``opgen.segmented_gaps``'s chunking.

    Returns ``(chunk_of_op, gap_seg)``: each op's owning idle-gap chunk
    id (N,), and each chunk's segment id (G,). Chunks are delimited
    exactly like ``segmented_gaps`` — a bound after every active op and
    at every segment start, so idle runs never merge across workload
    boundaries and empty segments own zero chunks. With this index the
    per-chunk gap values are ``segment_sum(idle, chunk_of_op, G)`` —
    shape-stable under ``jit`` — and per-(segment, knob) masked merges
    are ``segment_sum`` over ``gap_seg``.

    Depends only on the activity *pattern* (which ops use the
    component), not on service times, so one index per (stack,
    component) serves every NPU generation.
    """
    offsets = np.asarray(offsets, np.int64)
    n_seg = len(offsets) - 1
    idx = np.flatnonzero(active)
    bounds = np.union1d(offsets[:-1], idx + 1)
    if bounds.size == 0:  # no ops and no segments
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    n = len(active)
    chunk_of_op = np.searchsorted(bounds, np.arange(n), side="right") - 1
    gap_seg = np.minimum(np.searchsorted(offsets, bounds, side="right") - 1,
                         max(n_seg - 1, 0))
    return chunk_of_op.astype(np.int64), gap_seg.astype(np.int64)
