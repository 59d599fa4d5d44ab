"""The query generator: one seed, one stream of queries; the compiled
shapes never move with the seed."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import generator  # noqa: E402

SEEDS = (1, 2 ** 31 + 7, 4242)


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "bench", kind, name + ".json")) as fh:
        return json.load(fh)


CELLS = {"sweep": ("qwen2.5-14b", "sweep"), "plane": ("mamba2-780m", "plane")}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_same_seed_same_queries(cell):
    config, traffic = (_load("configs", CELLS[cell][0]),
                       _load("traffic", CELLS[cell][1]))
    a = [generator.query(config, traffic, 2 ** 31 + 11, i) for i in range(4)]
    b = [generator.query(config, traffic, 2 ** 31 + 11, i) for i in range(4)]
    c = [generator.query(config, traffic, 12, i) for i in range(4)]
    assert a == b
    assert a != c
    assert a[0] != a[1]


def test_knob_points_follow_the_canonical_order():
    from repro.core.policies import KnobGrid
    axes = {"delay_scale": [0.5, 2.0], "window_scale": [0.3, 1.5],
            "leak_off_logic": [0.02, 0.2], "leak_sram_off": [0.01]}
    want = [{k: getattr(p, k) for k in generator.KNOB_ORDER}
            for p in KnobGrid(**axes).product()]
    assert generator.knob_points(axes) == want


def _sweep_shapes(seed):
    from repro.core.opgen import stack_traces
    from repro.core.policies import _gap_indices, knob_pairs
    from bench.entries import sweep_grid
    config, traffic = _load("configs", "qwen2.5-14b"), _load("traffic",
                                                             "sweep")
    entry = sweep_grid.Entry(config, traffic)
    out = []
    for i in range(3):
        q = generator.query(config, traffic, seed, i)
        st = stack_traces(entry.build(q))
        gaps = {c: len(v[1]) for c, v in _gap_indices(st).items()}
        from repro.core.policies import KnobGrid
        triples, _ = knob_pairs(KnobGrid(**q["axes"]).product())
        out.append((st.n_ops, st.n_segments, tuple(sorted(gaps.items())),
                    len(triples), entry.size(q)))
    return out


def test_sweep_shapes_fixed_across_seeds():
    shapes = {s for seed in SEEDS for s in _sweep_shapes(seed)}
    assert len(shapes) == 1, shapes
    n_ops, n_seg, _gaps, n_triples, n_rec = shapes.pop()
    assert (n_seg, n_triples, n_rec) == (3, 6, 18000)


def test_plane_event_streams_fixed_across_seeds():
    from repro.core.hw import get_npu
    from repro.core.policies import KnobGrid, knob_pairs
    from repro.core.program_plane import _exec_rows
    from bench.entries import program_plane
    config, traffic = _load("configs", "mamba2-780m"), _load("traffic",
                                                             "plane")
    seen = set()
    for seed in SEEDS:
        entry = program_plane.Entry(config, traffic)
        q = generator.query(config, traffic, seed, 5)
        triples, _ = knob_pairs(KnobGrid(**q["axes"]).product())
        _pa, _rows, data = _exec_rows(entry.build(q),
                                      [get_npu(n) for n in q["npus"]],
                                      triples)
        seen.add((len(triples), data["cycle"].shape, entry.size(q)))
    assert len(seen) == 1, seen
    n_triples, (e_max, rows), n_rec = seen.pop()
    assert (n_triples, rows, n_rec) == (4, 80, 160)


def test_axis_draws_are_distinct_sorted_and_in_range():
    rng = generator.stream(2 ** 40 + 3, 0)
    for _ in range(50):
        v = generator._axis({"n": 6, "lo": 0.25, "hi": 8.0, "scale": "log"},
                            rng)
        assert v == sorted(v) and len(set(v)) == 6
        assert 0.25 <= v[0] and v[-1] <= 8.0
    assert generator._axis({"values": [1.0, 4.0]}, rng) == [1.0, 4.0]
    assert np.isfinite(generator.stream(-5, 1).uniform())
