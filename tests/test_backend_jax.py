"""JAX array backend vs the sweep kernel's numpy instantiation.

The jax backend must reproduce ``evaluate_batch`` on numpy (the same
backend-neutral kernel run eagerly on the host) — and the oracle
``sweep_reference`` (one scalar ``evaluate_reference`` per cell) —
record-for-record to ≤1e-9 relative on every numeric field: the full
acceptance grid (suite × 5 NPUs × 5 policies × 4 knobs), randomized
ragged stacks with empty and single-op workloads mixed in, knob grids
of size 1, and the ``sweep_grid`` fine-knob cross product with SA-width
variants. Also: the x64 requirement raises a
clear error instead of silently degrading to f32, and sharding the
stacked workload axis over a ``jax_compat`` mesh changes nothing.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.backend import gap_index, get_backend  # noqa: E402
from repro.core.hw import NPUS, get_npu  # noqa: E402
from repro.core.opgen import (Op, Workload, paper_suite,  # noqa: E402
                              segmented_gaps)
from repro.core.policies import (POLICIES, PolicyKnobs,  # noqa: E402
                                 evaluate_batch, evaluate_reference)
from repro.core.sweep import (sweep, sweep_grid,  # noqa: E402
                              sweep_reference)

from _sweep_equiv import RTOL  # noqa: E402
from _sweep_equiv import rel as _rel  # noqa: E402
from _sweep_equiv import assert_records_match as _assert_records_match  # noqa: E402,E501
from _sweep_equiv import assert_reports_match as _assert_reports_match  # noqa: E402,E501

KNOB_GRID = [
    PolicyKnobs(),
    PolicyKnobs(delay_scale=2.0),
    PolicyKnobs(delay_scale=0.5),
    PolicyKnobs(leak_off_logic=0.2, leak_sram_sleep=0.4,
                leak_sram_off=0.02),
]


# --------------------------------------------------------------------------
# acceptance grid: suite × 5 NPUs × 5 policies × 4 knobs
# --------------------------------------------------------------------------

def test_full_grid_matches_numpy_batched():
    """The ISSUE-4 acceptance grid, record-for-record ≤1e-9 with
    byte-identical ordering against the kernel on numpy."""
    suite = paper_suite()
    npus = tuple(NPUS)
    ref = sweep(suite, npus, POLICIES, KNOB_GRID, backend="numpy")
    got = sweep(suite, npus, POLICIES, KNOB_GRID, backend="jax")
    key = ("workload", "npu", "policy", "knob_idx")
    assert [tuple(r[k] for k in key) for r in ref] \
        == [tuple(r[k] for k in key) for r in got]
    _assert_records_match(ref, got)


def test_matches_sweep_reference_loop_oracle():
    """Agreement with the kernel on numpy is not enough: hold the jax
    backend directly to the oracle, one ``evaluate_reference`` per
    cell."""
    wls = paper_suite()[:3]
    grid = [PolicyKnobs(), PolicyKnobs(delay_scale=4.0)]
    ref = sweep_reference(wls, ("NPU-B", "NPU-E"), POLICIES, grid)
    got = sweep(wls, ("NPU-B", "NPU-E"), POLICIES, grid, backend="jax")
    _assert_records_match(ref, got)


# --------------------------------------------------------------------------
# randomized ragged stacks (empty + single-op workloads mixed in)
# --------------------------------------------------------------------------

def _random_workload(rng: np.random.Generator, i: int,
                     n_ops: int) -> Workload:
    ops = []
    for j in range(n_ops):
        kind = rng.random()
        flops_sa = float(rng.uniform(1e9, 5e12)) if kind < 0.45 else 0.0
        mm = None
        if flops_sa and rng.random() < 0.8:
            mm = (int(rng.integers(1, 4096)), int(rng.integers(1, 512)),
                  int(rng.integers(1, 4096)))
        ops.append(Op(
            f"op{j}", flops_sa=flops_sa,
            flops_vu=float(rng.uniform(1e8, 5e11))
            if rng.random() < 0.5 else 0.0,
            bytes_hbm=float(rng.uniform(1e6, 1e10))
            if rng.random() < 0.6 else 0.0,
            bytes_ici=float(rng.uniform(1e6, 1e9))
            if rng.random() < 0.15 else 0.0,
            sram_demand=int(rng.integers(0, 256 << 20)),
            matmul_dims=mm, count=int(rng.integers(1, 5))))
    return Workload(f"rand-{i}", "prefill", tuple(ops))


def test_randomized_ragged_stack_property():
    """Random ragged stack with empty and single-op workloads mixed in:
    the jax backend must match the per-workload scalar oracle
    ``evaluate_reference`` cell-for-cell (and the empty segments must
    come back as exact zeros)."""
    rng = np.random.default_rng(11)
    sizes = [0, 1, int(rng.integers(2, 30)), 0, 1,
             int(rng.integers(2, 30)), int(rng.integers(2, 30)), 0]
    wls = [_random_workload(rng, i, n) for i, n in enumerate(sizes)]
    grid = (PolicyKnobs(), PolicyKnobs(delay_scale=3.0),
            PolicyKnobs(leak_off_logic=0.0, delay_scale=0.25))
    npus = ("NPU-A", "NPU-E")
    res = evaluate_batch(wls, npus, POLICIES, grid, backend="jax")
    for wi, wl in enumerate(wls):
        for ai, npu in enumerate(npus):
            for pi, policy in enumerate(POLICIES):
                for ki, knobs in enumerate(grid):
                    want = evaluate_reference(wl, npu, policy, knobs)
                    got = res.report(wi, ai, pi, ki)
                    _assert_reports_match(got, want,
                                          (wl.name, npu, policy, ki))
                    if not wl.ops:
                        assert got.runtime_s == 0.0
                        assert got.total_j == 0.0
    for rec in res.records():
        for v in rec.values():
            if isinstance(v, float):
                assert np.isfinite(v)


def test_knob_grid_of_size_one_and_single_workload():
    wl = paper_suite()[8]
    ref = sweep(wl, ("NPU-C",), POLICIES,
                [PolicyKnobs(delay_scale=2.0)], backend="numpy")
    got = sweep(wl, ("NPU-C",), POLICIES,
                [PolicyKnobs(delay_scale=2.0)], backend="jax")
    assert len(got) == len(POLICIES)
    _assert_records_match(ref, got)


def test_no_workloads_empty_result():
    res = evaluate_batch([], ("NPU-D",), POLICIES, backend="jax")
    assert res.shape == (0, 1, len(POLICIES), 1)
    assert res.records() == []


# --------------------------------------------------------------------------
# sweep_grid fine-knob entry point
# --------------------------------------------------------------------------

def test_sweep_grid_cross_product_equivalence():
    """A small §6.5 cross product: jax matches numpy record-for-record
    and the knob metadata columns carry the delay-major ordering."""
    wls = paper_suite()[:2]
    kw = dict(delay_scale=(0.5, 1.0, 2.0),
              leak_off_logic=(0.03, 0.2),
              leak_sram_sleep=(0.25,),
              leak_sram_off=(0.002, 0.02))
    ref = sweep_grid(wls, ("NPU-D",), POLICIES, backend="numpy", **kw)
    got = sweep_grid(wls, ("NPU-D",), POLICIES, backend="jax", **kw)
    assert len(got) == 2 * 1 * len(POLICIES) * 12
    _assert_records_match(ref, got)
    # delay-major ordering: leak_sram_off innermost
    k0 = [r for r in got if r["workload"] == wls[0].name
          and r["policy"] == POLICIES[0]]
    assert [r["delay_scale"] for r in k0[:4]] == [0.5] * 4
    assert [r["leak_sram_off"] for r in k0[:4]] == [0.002, 0.02] * 2


def test_sweep_grid_sa_width_axis():
    """``sa_width`` is a real knob axis (ISSUE 5): the NPU axis stays
    untouched, records carry the width in their ``sa_width`` column,
    the traced-saw jax kernel matches the scalar oracle
    ``evaluate_reference`` both with the knob and on a width-replaced
    spec, and a non-native width genuinely changes the SA numbers."""
    wl = paper_suite()[4]  # prefill, SA-heavy
    res = sweep_grid(wl, ("NPU-D",), ("NoPG", "ReGate-HW"),
                     sa_width=(None, 256), backend="jax",
                     as_records=False)
    assert tuple(n.name for n in res.npus) == ("NPU-D",)
    recs = res.records()
    assert {r["npu"] for r in recs} == {"NPU-D"}
    assert {r["sa_width"] for r in recs} == {None, 256}
    native = [r for r in recs if r["sa_width"] is None
              and r["policy"] == "ReGate-HW"][0]
    wide = [r for r in recs if r["sa_width"] == 256
            and r["policy"] == "ReGate-HW"][0]
    assert native["runtime_s"] != wide["runtime_s"]
    # per-width cells equal the scalar oracle with the knob
    want = evaluate_reference(wl, "NPU-D", "ReGate-HW",
                              PolicyKnobs(sa_width=256))
    assert _rel(wide["total_j"], want.total_j) <= RTOL
    # ... and the oracle on the width-replaced spec (wider SA
    # also means higher peak FLOP/s — the derived sa_flops moved too)
    from repro.core.hw import with_sa_width
    spec = with_sa_width(get_npu("NPU-D"), 256)
    assert spec.sa_flops > get_npu("NPU-D").sa_flops
    want2 = evaluate_reference(wl, spec, "ReGate-HW")
    assert _rel(wide["total_j"], want2.total_j) <= RTOL


def test_sa_width_knob_traced_vs_loop_oracle():
    """A width × delay grid through the jax kernel against the
    per-cell loop oracle (``sweep_reference``), which resolves widths
    through memoized ``hw.with_sa_width`` variant specs."""
    from repro.core.sweep import knob_product
    wls = paper_suite()[:2]
    grid = knob_product(delay_scale=(1.0, 3.0),
                        sa_width=(None, 64, 512))
    ref = sweep_reference(wls, ("NPU-A", "NPU-E"), POLICIES, grid)
    got = sweep(wls, ("NPU-A", "NPU-E"), POLICIES, grid, backend="jax")
    _assert_records_match(ref, got)


# --------------------------------------------------------------------------
# sharding over the stacked workload axis (jax_compat mesh)
# --------------------------------------------------------------------------

def test_jax_mesh_sharded_matches_unsharded():
    from repro.parallel import jax_compat
    mesh = jax_compat.make_mesh((len(jax.devices()),), ("wl",))
    wls = paper_suite()[:3]
    ref = sweep(wls, ("NPU-A", "NPU-D"), POLICIES, KNOB_GRID,
                backend="numpy")
    got = evaluate_batch(wls, ("NPU-A", "NPU-D"), POLICIES, KNOB_GRID,
                         backend="jax", jax_mesh=mesh).records()
    _assert_records_match(ref, got)


def test_jax_mesh_requires_jax_backend():
    with pytest.raises(ValueError, match="jax_mesh"):
        evaluate_batch(paper_suite()[:1], backend="numpy",
                       jax_mesh=object())


@pytest.mark.parametrize("axes", [("knob",), ("wl", "knob")])
def test_shard_map_mesh_matches_numpy(axes):
    """A mesh with a ``"knob"`` axis selects the explicit shard_map
    program (op columns psum-completed over ``wl``, pairs + knobs
    sharded over ``knob``); every topology must match the kernel on
    numpy record-for-record — including knob/pair counts that do not
    divide the axis size (the padding path)."""
    from repro.core.sweep import knob_product
    from repro.parallel import jax_compat
    n_dev = len(jax.devices())
    shape = (n_dev,) if axes == ("knob",) else (1, n_dev)
    mesh = jax_compat.make_mesh(shape, axes)
    wls = paper_suite()[:3]
    grid = knob_product(delay_scale=(0.5, 1.0, 2.0),
                        leak_off_logic=(0.03, 0.2),
                        sa_width=(None, 256))
    ref = sweep(wls, ("NPU-B", "NPU-E"), POLICIES, grid,
                backend="numpy")
    got = evaluate_batch(wls, ("NPU-B", "NPU-E"), POLICIES, grid,
                         backend="jax", jax_mesh=mesh).records()
    _assert_records_match(ref, got)


# --------------------------------------------------------------------------
# x64 discipline
# --------------------------------------------------------------------------

def test_x64_disabled_raises_clear_error(monkeypatch):
    """If the compute scope's x64 switch fails to take effect, the jax
    backend must refuse loudly (f32 would silently violate the ≤1e-9
    contract) and tell the user how to enable x64."""
    from repro.parallel import jax_compat
    monkeypatch.setattr(jax_compat, "enable_x64",
                        lambda: jax.enable_x64(False))
    with pytest.raises(RuntimeError, match="x64"):
        evaluate_batch(paper_suite()[:1], ("NPU-D",), ("NoPG",),
                       backend="jax")


def test_compute_scope_yields_float64():
    """On the installed jax the scope switches x64 on by itself and
    restores the process setting on exit."""
    bk = get_backend("jax")
    outside = bk.x64_enabled()
    with bk.compute_scope():
        assert bk.asarray(np.zeros(3)).dtype == np.float64
        assert bk.xp.asarray(1.5).dtype == np.float64
        assert bk.xp.arange(3).dtype == np.int64
    assert bk.x64_enabled() == outside


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_rule(monkeypatch, tmp_path, env_set):
    """``JAX_COMPILATION_CACHE_DIR`` wins and is left to jax; without it
    the cache goes to the fixed ``<repo>/.jax_cache``."""
    from pathlib import Path

    from repro.parallel import jax_compat
    before = jax.config.jax_compilation_cache_dir
    repo_cache = Path(__file__).resolve().parents[1] / ".jax_cache"
    try:
        if env_set:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert jax_compat.use_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            got = jax_compat.use_compile_cache()
            assert got == str(repo_cache)
            assert jax.config.jax_compilation_cache_dir == got
            assert jax_compat.use_compile_cache() == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_default_backend_steering(monkeypatch):
    """``set_default_backend`` steers ``backend=None`` callers (what
    ``benchmarks/run.py --backend jax`` relies on)."""
    from repro.core import backend as backend_mod
    wl = paper_suite()[0]
    ref = sweep(wl, policies=("NoPG",), backend="numpy")
    prev = backend_mod.set_default_backend("jax")
    try:
        got = sweep(wl, policies=("NoPG",))
    finally:
        backend_mod.set_default_backend(prev)
    _assert_records_match(ref, got)


# --------------------------------------------------------------------------
# fixed-shape gap index vs the ragged reduceat oracle
# --------------------------------------------------------------------------

def test_gap_index_matches_segmented_gaps():
    """Per-segment masked gap sums computed through the fixed-shape
    index must equal the ragged ``segmented_gaps`` chunking for random
    activity patterns with empty segments mixed in."""
    rng = np.random.default_rng(5)
    for _ in range(20):
        lens = rng.integers(0, 9, size=int(rng.integers(1, 7)))
        offsets = np.zeros(len(lens) + 1, np.int64)
        np.cumsum(lens, out=offsets[1:])
        n = int(offsets[-1])
        active = rng.random(n) < 0.4
        idle = np.where(active, 0.0, rng.random(n))
        gv_ref, gofs = segmented_gaps(active, idle, offsets)
        chunk_of_op, gap_seg = gap_index(active, offsets)
        n_gaps = len(gap_seg)
        gv = np.bincount(chunk_of_op, weights=idle,
                         minlength=n_gaps)[:n_gaps]
        w = len(lens)
        for thresh in (0.0, 0.3, 1.5):
            mask_ref = gv_ref > thresh
            ref = np.array([np.where(mask_ref[gofs[s]:gofs[s + 1]],
                                     gv_ref[gofs[s]:gofs[s + 1]],
                                     0.0).sum() for s in range(w)])
            mask = gv > thresh
            got = np.bincount(gap_seg[mask], weights=gv[mask],
                              minlength=w)[:w]
            np.testing.assert_allclose(got, ref, rtol=1e-12, atol=0)
