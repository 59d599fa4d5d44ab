"""plane_host_ms: host milliseconds per query inside
``program_plane_batch`` (the program's ``regate.program_plane_batch``
spans) while no operation ran on the device, less the policy engine's
``regate.evaluate_batch`` nested in it: the plane's own executor rows,
scan transfers and dispatch, and folds. Nothing is returned where the
program opens no such span."""
from bench import program_spans


def read(red: dict):
    return program_spans.host_ms_per_query(
        red, ("regate.program_plane_batch",), outside="regate.evaluate_batch")
