"""dispatch_ms: host milliseconds per query inside the calls of the
sweep and event-scan kernels (the program's ``regate.sweep_kernel`` and
``regate.scan_kernel`` spans, each a kernel call and its block) while
no operation ran on the device: launching the kernel and waiting for it
to be scheduled. Nothing is returned where the program opens no such
span."""
from bench import program_spans


def read(red: dict):
    return program_spans.host_ms_per_query(
        red, ("regate.sweep_kernel", "regate.scan_kernel"))
