"""assemble_ms: host milliseconds per query filling the per-policy
result cubes from the harvested columns (the program's
``regate.assemble`` spans in ``policies._evaluate_batch_backend``).
Nothing is returned where the program opens no such span."""
from bench import program_spans


def read(red: dict):
    return program_spans.ms_per_query(red, "regate.assemble")
