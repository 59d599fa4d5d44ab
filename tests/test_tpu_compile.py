"""The jax path's kernels compile for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, one chip of it, at the ``paper_suite()`` stack shapes, with
x64 on. It refuses here what the chip's compiler would refuse (an
unsupported dtype, a program too large for device memory). The
topology is described inside a fixture, never at import, and the
persistent compile cache is off around these compiles: an entry
compiled for a described chip cannot be read back without one.
"""
import os
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chip_smoke import PLANE_GRID, PLANE_NPUS, SWEEP_AXES  # noqa: E402
from repro.core.backend import (get_backend, pack_slabs,  # noqa: E402
                                slab_layout)
from repro.core.hw import get_npu  # noqa: E402
from repro.core.opgen import paper_suite, stack_traces  # noqa: E402
from repro.core.policies import (POLICIES, KnobGrid,  # noqa: E402
                                 _backend_kernel, _host_columns,
                                 _knob_columns, knob_pairs)
from repro.core.program_plane import (_compiled, _exec_rows,  # noqa: E402
                                      _unit_major)
from repro.parallel import jax_compat  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _shapes(tree, sharding):
    """Shape/dtype stand-ins of a host pytree, placed on ``sharding``
    (built under x64, so float64 and int64 stay wide)."""
    def sds(x):
        a = np.asarray(x)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
    return jax.tree_util.tree_map(sds, tree)


def _slabs(tree):
    """A host pytree as the kernels take it: its ``slab_layout`` and
    the slabs ``put_slabs`` would put."""
    layout = slab_layout(tree)
    return layout, pack_slabs(tree, layout)


def test_sweep_kernel_compiles_for_one_v5e(one_chip):
    st = stack_traces(paper_suite())
    npu = get_npu("NPU-D")
    host, _ = _host_columns(st, npu)
    data_layout, data = _slabs(host)
    knob_layout, knobs = _slabs(
        _knob_columns(KnobGrid(**SWEEP_AXES).product(), npu))
    kern = _backend_kernel(get_backend("jax"))
    with jax_compat.enable_x64():
        compiled = kern.lower(_shapes(data, one_chip),
                              _shapes(knobs, one_chip),
                              tuple(POLICIES),
                              (data_layout, knob_layout)).compile()
    assert compiled.memory_analysis() is not None
    assert "f64" in compiled.as_text() or "F64" in compiled.as_text()


def test_event_scan_kernel_compiles_for_one_v5e(one_chip):
    triples, _ = knob_pairs(PLANE_GRID.product())
    _, _, data = _exec_rows(paper_suite(),
                            [get_npu(n) for n in PLANE_NPUS], triples)
    assert data["cycle"].dtype == np.int64
    layout, slabs = _slabs(_unit_major(data))
    assert sorted(slabs) == ["int64", "int8"]
    with jax_compat.enable_x64():
        compiled = _compiled(get_backend("jax")).lower(
            _shapes(slabs, one_chip), layout=layout).compile()
    mem = compiled.memory_analysis()
    # the unit axis rebuilt minor from a flat slab pads 32x: 0.5 GB of
    # temporaries here, against none with the slabs put unit-major
    assert mem.temp_size_in_bytes <= mem.argument_size_in_bytes
