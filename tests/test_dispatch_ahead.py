"""Dispatch ahead in ``evaluate_batch``: every NPU's sweep kernel is
launched before the first pull, so the host's pulls and assembly of one
NPU run while the device computes the next.

On the CPU jax backend (4 virtual host devices, in a subprocess: the
device count is fixed before jax initializes), for the single-device
path, the ``("wl",)`` GSPMD mesh and the ``"knob"`` shard_map mesh:

* a recording wrapper around the kernel call and ``to_numpy`` sees
  every launch before the first pull, one pull per NPU;
* the ``BatchResult`` of one 5-NPU call equals, bit for bit and cube for
  cube, the five one-NPU calls stacked on the NPU axis.

In process: a traced run reads ``in_flight`` from the ``regate.harvest``
spans beside the counts they already carried, and the numpy backend's
``copy_to_host_async`` is a no-op."""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.backend import NumpyBackend
from repro.core.opgen import paper_suite
from repro.core.policies import PolicyKnobs, evaluate_batch

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    import jax
    import numpy as np
    assert len(jax.devices()) == 4, jax.devices()
    from repro.core import policies
    from repro.core.backend import JaxBackend
    from repro.core.opgen import paper_suite
    from repro.core.policies import PolicyKnobs, evaluate_batch
    from repro.parallel import jax_compat

    MODE = sys.argv[1]
    mesh = {"single": None,
            "wl": jax_compat.make_mesh((4,), ("wl",)),
            "knob": jax_compat.make_mesh((2, 2), ("wl", "knob"))}[MODE]
    NPUS = ("NPU-A", "NPU-B", "NPU-C", "NPU-D", "NPU-E")
    POLS = ("NoPG", "ReGate-HW", "ReGate-Full")
    GRID = (PolicyKnobs(), PolicyKnobs(delay_scale=2.0, sa_width=64),
            PolicyKnobs(window_scale=0.5, leak_off_logic=0.2))
    wls = paper_suite()[:2]

    events = []
    to_numpy = JaxBackend.to_numpy

    def recording(make):
        def wrapped(*a, **k):
            fn = make(*a, **k)

            def call(*args):
                events.append("launch")
                return fn(*args)
            return call
        return wrapped

    def pull(self, x):
        events.append("pull")
        return to_numpy(self, x)

    policies._backend_kernel = recording(policies._backend_kernel)
    policies._shard_kernel = recording(policies._shard_kernel)
    JaxBackend.to_numpy = pull

    def run(npus):
        return evaluate_batch(wls, npus, POLS, GRID, backend="jax",
                              jax_mesh=mesh)

    run(NPUS)  # compile
    events.clear()
    whole = run(NPUS)
    assert events == ["launch"] * 5 + ["pull"] * 5, events
    ones = [run((n,)) for n in NPUS]

    def bits(a):
        return np.ascontiguousarray(a).view(np.uint64)

    def same(got, parts):
        want = np.stack([p[:, 0] for p in parts], axis=1)
        assert got.shape == want.shape, (got.shape, want.shape)
        assert np.array_equal(bits(got), bits(want))

    same(whole.runtime_s, [o.runtime_s for o in ones])
    for cube in ("static_j", "dynamic_j", "wake_events", "gated_s",
                 "setpm_by"):
        for c, got in getattr(whole, cube).items():
            same(got, [getattr(o, cube)[c] for o in ones])
    print("DISPATCH_AHEAD_OK", MODE)
""")


@pytest.mark.parametrize("mode", ("single", "wl", "knob"))
def test_every_kernel_launches_before_the_first_pull(mode):
    r = subprocess.run([sys.executable, "-c", _SCRIPT, mode],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert f"DISPATCH_AHEAD_OK {mode}" in r.stdout, r.stdout + r.stderr


def _harvest_stats(tmp_path, fn) -> list[dict]:
    """The stats of every ``regate.harvest`` span of ``fn()``, traced on
    the profiler, in the order the spans started."""
    import glob

    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(ev.start_ns, dict(ev.stats))
                           for ev in line.events
                           if ev.name == "regate.harvest"]
    return [st for _t, st in sorted(events, key=lambda e: e[0])]


def test_harvest_spans_count_the_kernels_in_flight(tmp_path):
    wls = paper_suite()[:2]
    grid = (PolicyKnobs(), PolicyKnobs(delay_scale=2.0))
    stats = _harvest_stats(tmp_path, lambda: evaluate_batch(
        wls, ("NPU-B", "NPU-D"), ("NoPG", "ReGate-Full"), grid,
        backend="jax"))
    assert [st["in_flight"] for st in stats] == [2, 1]
    for st in stats:
        assert st["arrays"] == 1 and st["bytes"] > 0
        assert st["ops"] > st["mm_ops"] > 0
        assert st["knobs"] == len(grid)


def test_numpy_copy_to_host_async_is_a_no_op():
    x = np.arange(6.0)
    assert NumpyBackend.copy_to_host_async(x) is None
    assert np.array_equal(x, np.arange(6.0))
