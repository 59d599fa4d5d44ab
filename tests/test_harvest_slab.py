"""One harvest pull per NPU.

The sweep program stacks its (K, W) output leaves into one
(n_out, K, W) float64 slab (``policies._pack``) and the host splits the
single copy back into views (``policies._unpack``). Pinned here: a jax
``evaluate_batch`` makes exactly one ``to_numpy`` per NPU on every path
(single device, the GSPMD ``("wl",)`` mesh, the ``shard_map`` meshes,
and the numpy instantiation of the same kernel); its records match the
kernel on numpy to ≤1e-9; and the slab reads back every leaf of the
unpacked kernel bit for bit.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import policies as pol_mod  # noqa: E402
from repro.core.backend import get_backend  # noqa: E402
from repro.core.hw import get_npu  # noqa: E402
from repro.core.opgen import paper_suite, stack_traces  # noqa: E402
from repro.core.policies import POLICIES, evaluate_batch  # noqa: E402
from repro.core.sweep import knob_product  # noqa: E402

from _sweep_equiv import assert_records_match  # noqa: E402

NPUS = ("NPU-B", "NPU-E")
GRID = knob_product(delay_scale=(0.5, 1.0, 2.0), leak_off_logic=(0.03, 0.2),
                    sa_width=(None, 256))
AXES = (None, ("wl",), ("knob",), ("wl", "knob"))


def _mesh(axes):
    from repro.parallel import jax_compat
    if axes is None:
        return None
    n = len(jax.devices())
    return jax_compat.make_mesh((n,) if len(axes) == 1 else (1, n), axes)


def _count_pulls(monkeypatch, name: str) -> dict:
    """Count the ``to_numpy`` calls of the backend ``name``. The class is
    patched, not the cached instance: undoing an instance patch would
    leave a bound method on the instance, shadowing later class
    patches of other tests in the process."""
    bk = get_backend(name)
    made = {"pulls": 0}
    pull = bk.to_numpy

    def to_numpy(self, x):
        made["pulls"] += 1
        return pull(x)

    monkeypatch.setattr(type(bk), "to_numpy", to_numpy)
    return made


@pytest.mark.parametrize("axes", AXES, ids=str)
def test_one_pull_per_npu(axes, monkeypatch):
    wls = paper_suite()[:3]
    ref = evaluate_batch(wls, NPUS, POLICIES, GRID, backend="numpy")
    made = _count_pulls(monkeypatch, "jax")
    got = evaluate_batch(wls, NPUS, POLICIES, GRID, backend="jax",
                         jax_mesh=_mesh(axes))
    assert made["pulls"] == len(NPUS)
    assert_records_match(ref.records(), got.records())


def test_numpy_instantiation_shares_the_packed_path(monkeypatch):
    wls = paper_suite()[:3]
    ref = evaluate_batch(wls, NPUS, POLICIES, GRID, backend="numpy")
    made = _count_pulls(monkeypatch, "numpy")
    got = pol_mod._evaluate_batch_backend(
        wls, tuple(get_npu(n) for n in NPUS), POLICIES, GRID,
        get_backend("numpy"))
    assert made["pulls"] == len(NPUS)
    assert_records_match(ref.records(), got.records())


def _programs(axes, bk, policies):
    """(packed program, unpacked program, its arguments) for one path;
    the unpacked program is the kernel as it runs with no ``_pack``."""
    mesh = _mesh(axes)
    st = stack_traces(paper_suite()[:3])
    npu = get_npu("NPU-E")

    def raw(data, knobs):
        return pol_mod._sweep_kernel(data, knobs, policies, bk)

    knob_host = pol_mod._knob_columns(GRID, npu)
    if axes is None:
        (data_layout, data), _ = pol_mod._backend_data(st, npu, bk)
        knob_layout, knobs = pol_mod._knob_arrays(GRID, npu, bk)
        kern = pol_mod._backend_kernel(bk)
        host, _ = pol_mod._host_columns(st, npu)
        return (lambda d, k: kern(d, k, policies,
                                  (data_layout, knob_layout))), \
            (lambda d, k: jax.jit(raw)(host, knob_host)), (data, knobs)
    sizes = bk.mesh_axis_sizes(mesh)
    data, _ = pol_mod._sharded_backend_data(st, npu, bk, sizes["wl"]
                                            if "wl" in sizes else 1)
    if axes == ("wl",):
        data = bk.shard_data(data, mesh)
        knob_layout, knobs = pol_mod._knob_arrays(GRID, npu, bk)
        kern = pol_mod._backend_kernel(bk)
        return (lambda d, k: kern(d, k, policies, (None, knob_layout))), \
            (lambda d, k: jax.jit(raw)(d, knob_host)), (data, knobs)
    wl_axis = "wl" if "wl" in sizes else None
    knobs = pol_mod._put_tree(
        pol_mod._knob_columns(GRID, npu, pad_to=sizes["knob"]), bk)
    packed = pol_mod._shard_kernel(bk, mesh, policies, wl_axis, "knob")
    spec = bk.pspec
    data_spec = {"op": spec(wl_axis) if wl_axis else spec(),
                 "gap_seg": spec(), "offsets": spec(), "scal": spec()}
    unpacked = bk.shard_map_kernel(
        lambda d, k: pol_mod._sweep_kernel(d, k, policies, bk,
                                           wl_axis=wl_axis,
                                           knob_axis="knob"),
        mesh, in_specs=(data_spec, spec("knob")), out_specs=spec("knob"))
    return packed, unpacked, (data, knobs)


@pytest.mark.parametrize("axes", AXES, ids=str)
def test_slab_reads_back_the_unpacked_kernel_bitwise(axes):
    bk = get_backend("jax")
    policies = tuple(POLICIES)
    k_n = len(GRID)
    with bk.compute_scope():
        packed, unpacked, args = _programs(axes, bk, policies)
        slab = np.asarray(packed(*args))
        leaves = unpacked(*args)
    layout = pol_mod._out_layout(policies)
    assert slab.dtype == np.float64 and slab.shape[0] == len(layout)
    got = pol_mod._unpack(slab[:, :k_n], policies)
    for path in layout:
        want, have = leaves, got
        for key in path:
            want, have = want[key], have[key]
        np.testing.assert_array_equal(have, np.asarray(want)[:k_n].T,
                                      err_msg=str(path))


def test_layout_covers_every_kernel_output():
    """Every leaf ``_sweep_kernel`` returns has a place in the slab, and
    only those (numpy instantiation: the structure is the jax one)."""
    bk = get_backend("numpy")
    st = stack_traces(paper_suite()[:2])
    npu = get_npu("NPU-D")
    for policies in (tuple(POLICIES), ("NoPG",), ("ReGate-HW", "Ideal")):
        data, _ = pol_mod._host_columns(st, npu)
        knobs = pol_mod._knob_columns(GRID, npu)
        out = pol_mod._sweep_kernel(data, knobs, policies, bk)
        paths = []

        def walk(tree, path):
            if isinstance(tree, dict):
                for k, v in tree.items():
                    walk(v, path + (k,))
            else:
                paths.append(path)

        walk(out, ())
        assert sorted(paths) == sorted(pol_mod._out_layout(policies))


_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    assert len(jax.devices()) == 8, jax.devices()
    import sys
    sys.path.insert(0, "tests")
    from _sweep_equiv import assert_records_match
    from repro.core.backend import JaxBackend
    from repro.core.opgen import paper_suite
    from repro.core.policies import POLICIES, evaluate_batch
    from repro.core.sweep import knob_product
    from repro.parallel import jax_compat

    pulls = [0]
    pull = JaxBackend.to_numpy

    def to_numpy(self, x):
        pulls[0] += 1
        return pull(self, x)

    JaxBackend.to_numpy = to_numpy
    wls = paper_suite()[:3]
    grid = knob_product(delay_scale=(0.25, 1.0, 4.0),
                        leak_off_logic=(0.03, 0.2), sa_width=(None, 64))
    npus = ("NPU-B", "NPU-E")
    ref = evaluate_batch(wls, npus, POLICIES, grid,
                         backend="numpy").records()
    for shape, axes in (((8,), ("wl",)), ((8,), ("knob",)),
                        ((2, 4), ("wl", "knob"))):
        mesh = jax_compat.make_mesh(shape, axes)
        pulls[0] = 0
        got = evaluate_batch(wls, npus, POLICIES, grid, backend="jax",
                             jax_mesh=mesh).records()
        assert pulls[0] == len(npus), (axes, pulls[0])
        assert_records_match(ref, got)
        print("mesh", shape, axes, "pulls", pulls[0])
    print("ONE_PULL_PER_NPU_OK")
""")


def test_one_pull_per_npu_on_8_virtual_devices():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", _SCRIPT], cwd=root,
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, PYTHONPATH="src",
                                JAX_PLATFORMS="cpu"))
    assert "ONE_PULL_PER_NPU_OK" in r.stdout, r.stdout + r.stderr
