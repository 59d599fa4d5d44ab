"""folds_ms: host milliseconds per query spent in the program plane's
closed-form VU burst and SRAM band folds (the program's
``regate.folds`` spans). Nothing is returned where the program opens no
such span."""
from bench import program_spans


def read(red: dict):
    return program_spans.ms_per_query(red, "regate.folds")
