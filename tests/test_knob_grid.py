"""KnobGrid / SweepSession API redesign (ISSUE 7).

The six sprawled knob-axis kwargs became one frozen ``KnobGrid`` value
and the four module-level substrate switches became the nestable
``SweepSession`` layer stack. These tests pin the compatibility
contract: the legacy spellings are thin shims over the new objects with
*identical* knob ordering and record tables (≤1e-9 relative,
``_sweep_equiv``), sessions scope and restore correctly, and the
record-table consumers (``with_savings`` / ``group_by``) never again
silently drop records that mix PR-5 (``sa_width``) and PR-6
(``window_scale``) axes — every sweep record carries every knob column
unconditionally, and a hand-built record missing one fails loudly.
"""
import pytest

from repro.core import session
from repro.core.backend import default_backend, set_default_backend
from repro.core.opgen import paper_suite
from repro.core.policies import KnobGrid, PolicyKnobs, as_knob_tuple
from repro.core.sa_gating import gating_cache_info
from repro.core.sweep import (SweepSession, group_by, knob_product,
                              sweep, sweep_grid, sweep_robustness,
                              with_savings)

from _sweep_equiv import assert_records_match as _assert_records_match

AXES = dict(delay_scale=(1.0, 2.0), leak_off_logic=(None, 0.2),
            leak_sram_sleep=(None,), leak_sram_off=(0.002,),
            sa_width=(None, 256), window_scale=(0.5, 1.0))


# --------------------------------------------------------------------------
# KnobGrid: the value object behind every knob-axis spelling
# --------------------------------------------------------------------------

def test_product_matches_knob_product():
    """The legacy kwargs shim and KnobGrid.product() are the same list,
    element for element — same knobs, same canonical ordering."""
    assert KnobGrid(**AXES).product() == knob_product(**AXES)
    assert KnobGrid().product() == [PolicyKnobs()]


def test_canonical_nesting_order():
    """sa_width outermost, then window_scale, then delay_scale, then
    the leak axes innermost — the ordering every sweep's knob_idx
    column has meant since ISSUE 5/6."""
    g = KnobGrid(sa_width=(None, 128), window_scale=(0.5, 1.0),
                 delay_scale=(1.0, 4.0), leak_off_logic=(None, 0.2))
    expect = [PolicyKnobs(sa_width=sw, window_scale=w, delay_scale=d,
                          leak_off_logic=lo)
              for sw in (None, 128) for w in (0.5, 1.0)
              for d in (1.0, 4.0) for lo in (None, 0.2)]
    assert g.product() == expect
    assert g.size == len(expect) == 16


def test_scalar_axes_coerce_to_singletons():
    g = KnobGrid(delay_scale=2.0, sa_width=128, window_scale=0.5,
                 leak_off_logic=0.1)
    assert g.delay_scale == (2.0,)
    assert g.sa_width == (128,)
    assert g.window_scale == (0.5,)
    assert g.leak_off_logic == (0.1,)
    assert g.size == 1


def test_columns_are_the_record_knob_columns():
    assert KnobGrid.columns() == ("delay_scale", "leak_off_logic",
                                  "leak_sram_sleep", "leak_sram_off",
                                  "sa_width", "window_scale")
    rec_keys = sweep(paper_suite()[:1], policies=("NoPG",))[0].keys()
    assert set(KnobGrid.columns()) | {"knob_idx"} <= set(rec_keys)


@pytest.mark.parametrize("bad", [
    dict(delay_scale=(0.0,)), dict(delay_scale=(float("nan"),)),
    dict(window_scale=(-1.0,)), dict(window_scale=()),
    dict(sa_width=(0,)), dict(sa_width=(1.5,)),
    dict(leak_off_logic=(-0.1,)),
    dict(leak_sram_off=(float("inf"),)),
])
def test_axis_validation(bad):
    with pytest.raises((ValueError, TypeError)):
        KnobGrid(**bad)


def test_as_knob_tuple_spellings():
    """None / flat sequence / KnobGrid all normalize to one tuple."""
    assert as_knob_tuple(None) == (PolicyKnobs(),)
    flat = [PolicyKnobs(), PolicyKnobs(delay_scale=2.0)]
    assert as_knob_tuple(flat) == tuple(flat)
    g = KnobGrid(**AXES)
    assert as_knob_tuple(g) == tuple(g.product())


# --------------------------------------------------------------------------
# sweep_grid: grid= vs the legacy axis kwargs
# --------------------------------------------------------------------------

def test_sweep_grid_grid_equals_kwargs():
    """grid=KnobGrid(...) and the six axis kwargs produce the same
    record table — same ordering metadata, every numeric ≤1e-9."""
    wls = paper_suite()[:2]
    pols = ("NoPG", "ReGate-Full")
    legacy = sweep_grid(wls, npus=("NPU-D",), policies=pols, **AXES)
    new = sweep_grid(wls, npus=("NPU-D",), policies=pols,
                     grid=KnobGrid(**AXES))
    key = ("workload", "npu", "policy", "knob_idx")
    assert [tuple(r[k] for k in key) for r in legacy] \
        == [tuple(r[k] for k in key) for r in new]
    _assert_records_match(legacy, new)


def test_sweep_grid_rejects_mixed_spellings():
    wls = paper_suite()[:1]
    with pytest.raises(ValueError, match="not both"):
        sweep_grid(wls, grid=KnobGrid(**AXES), delay_scale=(1.0, 2.0))
    with pytest.raises(TypeError, match="KnobGrid"):
        sweep_grid(wls, grid=[PolicyKnobs()])


# --------------------------------------------------------------------------
# record-table consumers: no silent drops, loud failures
# --------------------------------------------------------------------------

def test_mixed_axes_survive_savings_and_group_by():
    """The ISSUE 7 regression: records from a grid mixing the PR-5
    sa_width axis with the PR-6 window_scale axis used to be silently
    dropped by with_savings/group_by (missing columns). Every record
    must survive both, with a resolvable baseline."""
    wls = paper_suite()[:2]
    recs = sweep_grid(wls, policies=("NoPG", "ReGate-Full"),
                      grid=KnobGrid(sa_width=(None, 256),
                                    window_scale=(0.5, 1.0),
                                    delay_scale=(1.0, 2.0)))
    sv = with_savings(recs)
    assert len(sv) == len(recs) == len(wls) * 2 * 8
    assert all(r["savings"] is not None for r in sv)
    groups = group_by(sv, "sa_width", "window_scale")
    assert set(groups) == {(w, s) for w in (None, 256)
                           for s in (0.5, 1.0)}
    # nothing dropped: the groups partition the table
    assert sum(len(g) for g in groups.values()) == len(sv)


def test_missing_knob_column_fails_loudly():
    recs = sweep(paper_suite()[:1], policies=("NoPG", "ReGate-Full"))
    broken = [dict(r) for r in recs]
    del broken[1]["window_scale"]
    with pytest.raises(ValueError, match="window_scale"):
        with_savings(broken)
    with pytest.raises(KeyError, match="window_scale"):
        group_by(broken, "window_scale")


def test_robustness_records_carry_all_knob_columns():
    """Jitter-plane records feed the same consumers as any sweep's."""
    out = sweep_robustness(paper_suite()[:1], severities=(0.0, 1.0),
                           threshold_scales=(0.5, 1.0), seed=3)
    need = set(KnobGrid.columns()) | {"knob_idx"}
    assert all(need <= set(r) for r in out["records"])
    groups = group_by(out["records"], "window_scale")
    assert set(groups) == {(0.5,), (1.0,)}
    assert sum(len(g) for g in groups.values()) == len(out["records"])


# --------------------------------------------------------------------------
# SweepSession: scoping, nesting, legacy-setter delegation
# --------------------------------------------------------------------------

def test_session_scopes_and_nests():
    assert default_backend() == "numpy"
    with SweepSession(backend="jax") as outer:
        assert default_backend() == "jax"
        assert session.resolve("jax_mesh") is None
        with SweepSession(backend="numpy", gating_cache_size=64):
            assert default_backend() == "numpy"
            assert session.resolve("gating_cache_size") == 64
        assert default_backend() == "jax"
        assert session.resolve("gating_cache_size") is None
        assert outer is not None
    assert default_backend() == "numpy"


def test_session_exception_safe():
    with pytest.raises(RuntimeError, match="boom"):
        with SweepSession(backend="jax"):
            raise RuntimeError("boom")
    assert default_backend() == "numpy"


def test_legacy_setters_write_the_root_layer():
    """set_default_backend under an active session mutates the root:
    the session keeps winning until it exits, then the new root default
    shows through — old call sites keep working, sessions stay
    strongest."""
    try:
        with SweepSession(backend="numpy"):
            prev = set_default_backend("jax")
            assert prev == "numpy"
            assert default_backend() == "numpy"  # session shadows root
        assert default_backend() == "jax"
    finally:
        set_default_backend("numpy")
    assert default_backend() == "numpy"


def test_gating_cache_size_scoped():
    before = gating_cache_info().maxsize
    with SweepSession(gating_cache_size=128):
        assert gating_cache_info().maxsize == 128
        with SweepSession(gating_cache_size=None):
            assert gating_cache_info().maxsize is None
        assert gating_cache_info().maxsize == 128
    assert gating_cache_info().maxsize == before


def test_session_validation_and_reentrancy():
    with pytest.raises(KeyError, match="unknown array backend"):
        SweepSession(backend="torch")
    with pytest.raises(ValueError, match="GuardPolicy"):
        SweepSession(guard="strict")
    s = SweepSession(backend="numpy")
    with s:
        with pytest.raises(RuntimeError, match="not re-entrant"):
            s.__enter__()
    with pytest.raises(KeyError, match="unknown session field"):
        session.set_root(frobnicate=1)
    with pytest.raises(KeyError, match="unknown session field"):
        session.resolve("frobnicate")


def test_sweeps_ride_the_session_backend():
    """A sweep with backend=None inside SweepSession(backend=...) is
    the same computation as passing the backend explicitly."""
    wls = paper_suite()[:1]
    grid = KnobGrid(window_scale=(0.5, 1.0))
    explicit = sweep_grid(wls, policies=("NoPG", "ReGate-HW"),
                          grid=grid, backend="jax")
    with SweepSession(backend="jax"):
        implicit = sweep_grid(wls, policies=("NoPG", "ReGate-HW"),
                              grid=grid)
    _assert_records_match(explicit, implicit)
