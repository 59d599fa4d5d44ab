"""One put per dtype per call.

The sweep kernel's stack columns and knob grid, and the event scan's
dense stack, are packed on the host into one flat array per dtype
(``backend.put_slabs``) and rebuilt inside the jitted program by static
slices and reshapes (``backend.unpack_slabs``). Pinned here: the round
trip gives back every leaf with its key path, dtype, shape and bits; the
packed kernels' outputs equal the per-leaf kernels' bit for bit on the
jax CPU backend (dense, SSM and MLA/MoE stacks on NPU-A..E, and the
event scan); a jax ``evaluate_batch`` puts at most 4 arrays per NPU,
and new stacks of the same shapes and other NPU generations compile
nothing new.
"""
import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.configs import get_arch  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.core import policies as pol_mod  # noqa: E402
from repro.core import program_plane as pp  # noqa: E402
from repro.core.backend import (JaxBackend, get_backend,  # noqa: E402
                                pack_slabs, put_slabs, slab_counts,
                                slab_layout, transfer_counts, unpack_slabs)
from repro.core.hw import get_npu  # noqa: E402
from repro.core.opgen import arch_workload, paper_suite, \
    stack_traces  # noqa: E402
from repro.core.policies import (POLICIES, evaluate_batch,  # noqa: E402
                                 knob_pairs)
from repro.core.program_plane import program_plane_batch  # noqa: E402
from repro.core.sweep import knob_product  # noqa: E402

NPUS = ("NPU-A", "NPU-B", "NPU-C", "NPU-D", "NPU-E")
GRID = knob_product(delay_scale=(0.5, 2.0), leak_off_logic=(0.03, 0.2),
                    sa_width=(None, 64))
DECODE = ShapeConfig("decode_32k", 32768, 64, "decode")


def _leaves(tree, path=()):
    """``{key path: 0-d or n-d numpy array}`` of a dict pytree."""
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(_leaves(v, path + (k,)))
        return out
    return {path: np.asarray(tree)}


def _assert_same_leaves(got, want):
    got, want = _leaves(got), _leaves(want)
    assert sorted(got) == sorted(want)
    for path, w in want.items():
        g = got[path]
        assert (g.dtype, g.shape) == (w.dtype, w.shape), path
        assert g.tobytes() == w.tobytes(), path


def _dense():
    triples, _ = knob_pairs(GRID)
    return pp._exec_rows(paper_suite()[:2], [get_npu("NPU-B")],
                         triples)[2]


def _trees():
    st = stack_traces(paper_suite()[:3])
    host, _ = pol_mod._host_columns(st, get_npu("NPU-A"))
    empty, _ = pol_mod._host_columns(stack_traces([]), get_npu("NPU-E"))
    dense = _dense()
    return {
        "host_columns": host,
        "host_columns_empty_stack": empty,
        "knob_columns": pol_mod._knob_columns(GRID, get_npu("NPU-D")),
        "knob_columns_pad_to_3": pol_mod._knob_columns(
            GRID, get_npu("NPU-D"), pad_to=3),
        "pack_dense": dense,
        "pack_dense_unit_major": pp._unit_major(dense),
        "scalars_and_edges": {
            "f": 0.5, "i": 7, "neg0": -0.0, "nan": np.nan,
            "b0": np.bool_(True), "b": np.array([True, False, True]),
            "i8": np.array([[-128, 127]], np.int8), "e": np.zeros((0, 3)),
            "nest": {"z": np.zeros(0, np.int64), "a": np.arange(6.0)
                     .reshape(2, 3)}},
    }


TREES = _trees()


@pytest.mark.parametrize("name", sorted(TREES))
def test_round_trip_gives_back_every_leaf(name):
    tree = TREES[name]
    layout = slab_layout(tree)
    slabs = pack_slabs(tree, layout)
    assert sorted(slabs) == sorted(d for d, _n in layout[0])
    assert all(s.ndim == 1 for s in slabs.values())
    assert "bool" not in slabs
    _assert_same_leaves(unpack_slabs(slabs, layout), tree)
    # the same slices and reshapes, traced on jax
    bk = get_backend("jax")
    with bk.compute_scope():
        got = jax.jit(lambda s: unpack_slabs(s, layout))(
            {d: bk.asarray(s) for d, s in slabs.items()})
        _assert_same_leaves(jax.tree_util.tree_map(np.asarray, got), tree)


def test_layout_depends_only_on_keys_dtypes_and_shapes():
    wls = paper_suite()[:3]
    st = stack_traces(wls)
    fresh = stack_traces([dataclasses.replace(w) for w in wls])
    assert fresh is not st
    layouts = {slab_layout(pol_mod._host_columns(s, get_npu(n))[0])
               for s in (st, fresh) for n in NPUS}
    assert len(layouts) == 1
    (layout,) = layouts
    hash(layout)
    # the stack columns go as one float64 and one int64 slab, has_mm in
    # the int64 one; the knob grid likewise; the scan's as int64 + int8
    assert [d for d, _n in layout[0]] == ["int64", "float64"]
    assert [d for d, _n in slab_layout(
        pol_mod._knob_columns(GRID, get_npu("NPU-A")))[0]] == [
        "float64", "int64"]
    assert sorted(d for d, _n in slab_layout(_dense())[0]) == [
        "int64", "int8"]


def _stacks():
    return {
        "dense": [arch_workload(get_arch("qwen2.5-14b"), DECODE, n_chips=256,
                                tp=16)],
        "ssm": [arch_workload(get_arch("mamba2-780m"), DECODE, n_chips=8,
                              tp=1)],
        "mla_moe": [arch_workload(get_arch("deepseek-v2-236b"), DECODE,
                                  n_chips=32, tp=1)],
    }


@pytest.mark.parametrize("family", ("dense", "ssm", "mla_moe"))
def test_packed_sweep_kernel_is_bitwise_the_per_leaf_kernel(family):
    """The packed inputs against the per-leaf ones (Python scalars put
    as weak-typed 0-d arrays), through the same jitted kernel."""
    bk = get_backend("jax")
    st = stack_traces(_stacks()[family])
    policies = tuple(POLICIES)
    kern = pol_mod._backend_kernel(bk)
    with bk.compute_scope():
        for name in NPUS:
            npu = get_npu(name)
            host, _ = pol_mod._host_columns(st, npu)
            knob_host = pol_mod._knob_columns(GRID, npu)
            (data_layout, data), _ = pol_mod._backend_data(st, npu, bk)
            knob_layout, knobs = pol_mod._knob_arrays(GRID, npu, bk)
            packed = np.asarray(kern(data, knobs, policies,
                                     (data_layout, knob_layout)))
            leaf = np.asarray(kern(pol_mod._put_tree(host, bk),
                                   pol_mod._put_tree(knob_host, bk),
                                   policies))
            assert packed.dtype == leaf.dtype == np.float64
            assert packed.shape == leaf.shape
            assert packed.tobytes() == leaf.tobytes(), name


def test_packed_event_scan_is_bitwise_the_per_leaf_scan():
    bk = get_backend("jax")
    data = _dense()
    got = pp._run_kernel(data, bk)
    with bk.compute_scope():
        leaf = pp._compiled(bk)({k: bk.asarray(v) for k, v in data.items()})
        want = {k: np.asarray(v) for k, v in leaf.items()}
    _assert_same_leaves(got, want)
    # and the numpy instantiation of the same unpack and scan
    _assert_same_leaves(pp._run_kernel(data, get_backend("numpy")), want)


def _count_puts(monkeypatch) -> dict:
    made = {"puts": 0}
    put = JaxBackend.asarray

    def asarray(self, x):
        made["puts"] += 1
        return put(self, x)

    monkeypatch.setattr(JaxBackend, "asarray", asarray)
    return made


def _fresh(n: int) -> list:
    """New workload objects, so no stack of them is on the device yet."""
    return [dataclasses.replace(w) for w in paper_suite()[:n]]


def test_at_most_four_puts_per_npu_and_no_recompile(monkeypatch):
    bk = get_backend("jax")
    kern = pol_mod._backend_kernel(bk)
    made = _count_puts(monkeypatch)
    npus = ("NPU-B", "NPU-E")
    ref = evaluate_batch(_fresh(3), npus, POLICIES, GRID, backend="jax")
    assert 0 < made["puts"] <= 4 * len(npus)
    compiled = kern._cache_size()
    for pair in (("NPU-A", "NPU-C"), ("NPU-D", "NPU-B")):
        made["puts"] = 0
        got = evaluate_batch(_fresh(3), pair, POLICIES, GRID,
                             backend="jax")
        assert 0 < made["puts"] <= 4 * len(pair)
        assert kern._cache_size() == compiled
    assert got.runtime_s.shape == ref.runtime_s.shape


def test_event_scan_puts_two_slabs_and_does_not_recompile(monkeypatch):
    bk = get_backend("jax")
    npus = ("NPU-B", "NPU-D")
    program_plane_batch(_fresh(2), npus, GRID, backend="jax")
    scan = pp._compiled(bk)
    compiled = scan._cache_size()
    made = _count_puts(monkeypatch)
    res = program_plane_batch(_fresh(2), npus, GRID, backend="jax")
    # the policy side: 2 stack + 2 knob slabs per NPU; the scan: 2 slabs
    assert 0 < made["puts"] <= 4 * len(npus) + 2
    assert scan._cache_size() == compiled
    assert res.cycles.shape[1] == len(npus)


def test_put_slabs_counts_what_it_puts(monkeypatch):
    made = _count_puts(monkeypatch)
    bk = get_backend("jax")
    tree = TREES["scalars_and_edges"]
    with bk.compute_scope():
        layout, dev = put_slabs(tree, bk)
    assert made["puts"] == len(dev) == len(layout[0])
    assert slab_counts(layout) == transfer_counts(pack_slabs(tree, layout))
    assert sorted(dev) == ["float64", "int64", "int8"]
