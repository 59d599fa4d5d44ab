"""Stable names of the program's kernels, and the XLA program names they
carry in a device trace today.

The names come from the Python functions jax compiles:
``policies._backend_kernel`` jits ``kern`` (the sweep kernel,
``policies._sweep_kernel``) and ``program_plane._compiled`` jits
``body`` (the event-scan kernel, ``program_plane._full_body``). A
program change that renames either function changes the pattern here,
and nowhere else.
"""
KERNELS = {
    "sweep_kernel": r"^jit_kern\b",
    "scan_kernel": r"^jit_body\b",
}
