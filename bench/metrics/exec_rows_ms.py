"""exec_rows_ms: host milliseconds per query spent building the
event-scan kernel's rows (the program's ``regate.exec_rows`` spans,
``program_plane._exec_rows``: lowering lookups, the event stack and its
dense packing). Nothing is returned where the program opens no such
span."""
from bench import program_spans


def read(red: dict):
    return program_spans.ms_per_query(red, "regate.exec_rows")
