"""Multi-device shard_map sweep on 8 virtual host devices (subprocess:
device count must be fixed before jax initializes). The ISSUE-5
equivalence contract on a real multi-device topology: the shard_map
program — op columns sharded over ``"wl"`` with in-kernel psums, unique
(saw, delay) pairs + knob grid sharded over ``"knob"`` — must match the
kernel on numpy record-for-record ≤1e-9 on every mesh shape, including
axis sizes that do not divide the op/pair/knob counts (padding)."""
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    assert len(jax.devices()) == 8, jax.devices()
    import sys
    sys.path.insert(0, "tests")
    from _sweep_equiv import assert_records_match
    from repro.core.opgen import paper_suite
    from repro.core.policies import POLICIES, evaluate_batch
    from repro.core.sweep import knob_product, sweep
    from repro.parallel import jax_compat

    wls = paper_suite()[:4]
    grid = knob_product(delay_scale=(0.25, 1.0, 4.0),
                        leak_off_logic=(0.03, 0.2),
                        sa_width=(None, 256, 64))
    ref = sweep(wls, ("NPU-B", "NPU-E"), POLICIES, grid,
                backend="numpy")
    for shape, axes in (((8,), ("knob",)),
                        ((2, 4), ("wl", "knob")),
                        ((8, 1), ("wl", "knob"))):
        mesh = jax_compat.make_mesh(shape, axes)
        got = evaluate_batch(wls, ("NPU-B", "NPU-E"), POLICIES, grid,
                             backend="jax", jax_mesh=mesh).records()
        assert_records_match(ref, got)
        print("mesh", shape, axes, "ok")
    print("MULTIDEVICE_SWEEP_OK")
""")


def test_shard_map_sweep_on_8_virtual_devices():
    r = subprocess.run([sys.executable, "-c", _SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert "MULTIDEVICE_SWEEP_OK" in r.stdout, r.stdout + r.stderr


# the ISSUE-10 program plane on the same 8-device topology: the event
# scan kernel's row axis is GSPMD-sharded over "wl" (inert padding rows
# make 20 exec rows divide 8 devices) and must match the single-device
# jax run bit-for-bit
_SCRIPT_PLANE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    assert len(jax.devices()) == 8, jax.devices()
    from repro.core.opgen import paper_suite
    from repro.core.policies import KnobGrid
    from repro.core.sweep import sweep_program_plane
    from repro.parallel import jax_compat

    wls = paper_suite()[:5]
    grid = KnobGrid(delay_scale=(1.0, 4.0), window_scale=(1.0, 0.5))
    npus = ("NPU-B", "NPU-D")
    one = sweep_program_plane(wls, npus=npus, knob_grid=grid,
                              backend="jax")
    mesh = jax_compat.sweep_mesh(wl=8)
    got = sweep_program_plane(wls, npus=npus, knob_grid=grid,
                              backend="jax", jax_mesh=mesh)
    assert len(one) == len(got) == len(wls) * 2 * 4
    for x, y in zip(one, got):
        for k in x:
            assert x[k] == y[k] or (
                isinstance(x[k], float)
                and abs(x[k] - y[k]) <= 1e-9 * max(1.0, abs(x[k]))), \\
                (k, x[k], y[k])
    print("MULTIDEVICE_PLANE_OK")
""")


def test_program_plane_mesh_on_8_virtual_devices():
    r = subprocess.run([sys.executable, "-c", _SCRIPT_PLANE],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert "MULTIDEVICE_PLANE_OK" in r.stdout, r.stdout + r.stderr
