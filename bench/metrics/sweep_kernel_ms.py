"""sweep_kernel_ms: device milliseconds per query of the sweep kernel's
XLA program (named in ``bench/kernels.py``), from the device trace.
Nothing is returned where the kernel did not run."""


def read(red: dict):
    t = red["kernel_ns"].get("sweep_kernel")
    if not t or not red["queries"]:
        return None
    return t / 1e6 / red["queries"]
