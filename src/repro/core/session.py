"""Session-scoped sweep configuration (ISSUE 7).

The sweep substrate used to be configured through independent
module-level switches threaded ad hoc through every entry point:
``backend.set_default_backend`` (what ``backend=None`` resolves to),
a ``jax_mesh=`` kwarg repeated on each call, and
``sa_gating.set_gating_cache_size``. ``SweepSession`` consolidates them
into one context object::

    with SweepSession(backend="jax", jax_mesh=mesh):
        recs = sweep_grid(suite, grid=grid)   # rides the session

A session is a *layer*: fields left at ``UNSET`` inherit from the
enclosing session (ultimately the root session, which holds the
process-wide defaults the legacy setters mutate). Sessions nest — an
inner ``SweepSession(backend="numpy")`` temporarily pins the backend
while still inheriting the outer session's mesh — and restore the
previous state on exit, exception-safe.

Compatibility contract:

* ``backend.default_backend()`` / ``backend.set_default_backend`` now
  read/write the ROOT session, so old call sites keep working; while a
  session that pins the same field is active, the session wins (the
  setter still records the new root default, visible once the session
  exits).
* ``gating_cache_size`` is applied on ``__enter__`` via
  ``sa_gating.set_gating_cache_size`` (the LRU itself stays the single
  source of truth) and the previous size is restored on ``__exit__``.
* ``jax_mesh`` is consulted by ``policies.evaluate_batch`` whenever its
  ``jax_mesh=`` argument is ``None`` — but only when the effective
  backend is jax, so a numpy sweep inside a mesh session stays valid.
"""
from __future__ import annotations

import threading
from typing import Any, Optional


class _Unset:
    """Sentinel: 'inherit this field from the enclosing session'."""

    _instance: Optional["_Unset"] = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
        return cls._instance

    def __repr__(self) -> str:  # pragma: no cover - repr cosmetics
        return "<inherit>"


UNSET = _Unset()

_FIELDS = ("backend", "jax_mesh", "gating_cache_size", "guard")


class SweepSession:
    """One configuration layer for the sweep substrate.

    Parameters all default to ``UNSET`` (inherit). ``backend`` must be
    one of ``backend.BACKEND_NAMES``; ``gating_cache_size`` a cache size
    accepted by ``sa_gating.set_gating_cache_size`` (``None`` =
    unbounded); ``guard`` a ``guard.GuardPolicy`` (or ``None``) that
    campaign entry points (``sweep_fleet`` / ``sweep_chaos``) pick up
    when their ``guard=`` argument is left unset — scoping the guard
    plane's watchdog/failover/quarantine machinery exactly like the
    backend. Use as a context manager; re-entering an already-active
    session raises.
    """

    def __init__(self, backend: Any = UNSET, jax_mesh: Any = UNSET,
                 gating_cache_size: Any = UNSET, guard: Any = UNSET):
        if backend is not UNSET:
            _check_backend(backend)
        if guard is not UNSET:
            _check_guard(guard)
        self.backend = backend
        self.jax_mesh = jax_mesh
        self.gating_cache_size = gating_cache_size
        self.guard = guard
        self._active = False
        self._prev_cache: Any = UNSET

    def __repr__(self) -> str:
        parts = [f"{f}={getattr(self, f)!r}" for f in _FIELDS
                 if getattr(self, f) is not UNSET]
        return f"SweepSession({', '.join(parts)})"

    # -- context management -------------------------------------------
    def __enter__(self) -> "SweepSession":
        if self._active:
            raise RuntimeError("SweepSession is not re-entrant; "
                               "construct a new one per `with` block")
        _stack().append(self)
        self._active = True
        if self.gating_cache_size is not UNSET:
            from repro.core import sa_gating
            self._prev_cache = sa_gating.set_gating_cache_size(
                self.gating_cache_size)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        stack = _stack()
        if not self._active or stack[-1] is not self:
            raise RuntimeError(
                "SweepSession exited out of order (not the innermost "
                "active session)")
        if self._prev_cache is not UNSET:
            from repro.core import sa_gating
            sa_gating.set_gating_cache_size(self._prev_cache)
            self._prev_cache = UNSET
        stack.pop()
        self._active = False


def _check_backend(name: str) -> str:
    from repro.core.backend import BACKEND_NAMES
    if name not in BACKEND_NAMES:
        raise KeyError(f"unknown array backend {name!r}; "
                       f"have {BACKEND_NAMES}")
    return name


def _check_guard(value: Any) -> Any:
    from repro.core.guard import GuardPolicy
    if value is not None and not isinstance(value, GuardPolicy):
        raise ValueError(f"guard must be a guard.GuardPolicy or None, "
                         f"got {type(value)}")
    return value


# -----------------------------------------------------------------------
# the session stack: [root, outer, ..., innermost]
# -----------------------------------------------------------------------

def _root() -> SweepSession:
    """The process-wide defaults layer (what the legacy setters mutate).

    The gating-cache size intentionally stays UNSET at the root: the
    LRU in ``sa_gating`` is its own source of truth and sessions scope
    it by save/restore rather than by resolution.
    """
    # bypass __init__ validation: the root is built at import time and
    # validation would import repro.core.backend mid-initialization
    s = object.__new__(SweepSession)
    s.backend = "numpy"
    s.jax_mesh = None
    s.gating_cache_size = UNSET
    s.guard = None
    s._active = True  # the root never exits
    s._prev_cache = UNSET
    return s


_LOCAL = threading.local()


def _stack() -> list:
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = [_ROOT]
        _LOCAL.stack = st
    return st


_ROOT = _root()


def resolve(field: str) -> Any:
    """Innermost non-UNSET value for ``field`` (walks the stack down to
    the root, which always holds a concrete value for resolvable
    fields)."""
    if field not in _FIELDS:
        raise KeyError(f"unknown session field {field!r}; have {_FIELDS}")
    for layer in reversed(_stack()):
        v = getattr(layer, field)
        if v is not UNSET:
            return v
    return None  # gating_cache_size: root holds UNSET by design


def current() -> dict:
    """Resolved view of the active session state (one value per field)."""
    return {f: resolve(f) for f in _FIELDS}


def set_root(**fields: Any) -> dict:
    """Mutate the root (process-default) layer; returns the previous
    root values. This is what the legacy module-level setters delegate
    to — an active session that pins the same field still shadows the
    new root value until it exits."""
    prev = {}
    for name, value in fields.items():
        if name not in _FIELDS:
            raise KeyError(f"unknown session field {name!r}; "
                           f"have {_FIELDS}")
        if name == "backend":
            _check_backend(value)
        elif name == "guard":
            _check_guard(value)
        prev[name] = getattr(_ROOT, name)
        setattr(_ROOT, name, value)
    return prev
