"""harvest_ms: host milliseconds per query spent pulling kernel outputs
back to the host (the program's ``regate.harvest`` spans: the per-NPU
pulls after the sweep kernel and the event-scan kernel's outputs).
Nothing is returned where the program opens no such span."""
from bench import program_spans


def read(red: dict):
    return program_spans.ms_per_query(red, "regate.harvest")
