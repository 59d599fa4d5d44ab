"""The one place for jax APIs that drift between releases.

The repo targets the installed jax (0.9). Every spelling of the mesh,
sharding, x64 and compile-cache surface that has moved before funnels
through this module, so the model, launch and sweep code never spells
them directly:

* ``make_mesh(shape, axes)``        — Auto axis types on every axis;
* ``set_mesh(mesh)``                — context manager activating a mesh
  for GSPMD sharding constraints;
* ``get_abstract_mesh()``           — the active mesh or ``None``;
* ``mesh_axis_sizes(mesh)``         — ``{axis: size}`` for abstract and
  physical meshes alike;
* ``shard_map(...)``                — the replication-check kwarg;
* ``enable_x64()``                  — scoped float64/int64 (the sweep
  and program-plane kernels run entirely under it);
* ``use_compile_cache()``           — the persistent compile-cache rule
  of the entry points.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path
from typing import Optional

import jax

# <repo>/.jax_cache: a fixed path, so a later process finds what an
# earlier one compiled (the path is part of the cache key)
REPO_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    kwargs = {"axis_types": (jax.sharding.AxisType.Auto,) * len(axes)}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(shape, axes, **kwargs)


@contextlib.contextmanager
def set_mesh(mesh):
    """Activate ``mesh`` for sharding constraints inside jit."""
    with jax.set_mesh(mesh):
        yield mesh


def get_abstract_mesh() -> Optional[object]:
    """The mesh active for GSPMD lowering, or ``None`` when unset/empty.

    Callers treat ``None`` as "single device, skip constraints", which
    keeps smoke tests mesh-free.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    return mesh


def mesh_axis_sizes(mesh) -> dict[str, int]:
    """``{axis_name: size}`` for abstract and physical meshes alike."""
    if mesh is None:
        return {}
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the varying-manual-axes check switched by
    ``check``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def enable_x64():
    """Context manager: float64/int64 jax arrays inside, the process
    setting restored on exit."""
    return jax.enable_x64(True)


def use_compile_cache() -> Optional[str]:
    """Point jax's persistent compile cache at a fixed directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    this leaves the setting alone. Otherwise the cache goes to
    ``<repo>/.jax_cache`` (listed in ``.gitignore``). Returns the
    directory in use. Entry points call this once, before their first
    compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_COMPILE_CACHE))
    return str(REPO_COMPILE_CACHE)


def sweep_mesh(wl: int = 1, knob: int = 1, *, devices=None):
    """Mesh for the multi-device sweep plane (ISSUE 5), axes named the
    way ``policies._evaluate_batch_backend`` dispatches on them:

    * ``wl``   — shards the stacked per-op axis (GSPMD when it is the
      only axis; inside the ``shard_map`` program otherwise);
    * ``knob`` — presence selects the explicit ``shard_map`` path and
      shards the unique-width / (width, delay)-pair / knob axes.

    So ``sweep_mesh(wl=8)`` is the pure-GSPMD data-sharding mesh (no
    knob axis is added), while any ``knob >= 1`` request — including
    the degenerate ``(wl=1, knob=1)`` the in-process tests use to
    cover the shard_map program on one device — yields a
    ``("wl", "knob")`` mesh and the explicit SPMD path.
    ``wl * knob`` must not exceed the available device count.
    """
    if knob == 1 and wl > 1:
        return make_mesh((wl,), ("wl",), devices=devices)
    return make_mesh((wl, knob), ("wl", "knob"), devices=devices)
