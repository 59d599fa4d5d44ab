"""host_columns_ms: host milliseconds per query spent building the
sweep kernel's input columns of a stack not yet on the device (the
program's ``regate.host_columns`` spans, ``policies._host_columns``).
Nothing is returned where every stack was already on the device."""
from bench import program_spans


def read(red: dict):
    return program_spans.ms_per_query(red, "regate.host_columns")
