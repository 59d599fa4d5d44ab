"""arch_workload_ms: host milliseconds per query spent tracing the
query's workloads (the program's ``regate.arch_workload`` spans, one a
``opgen.arch_workload`` call): ``opgen_ms`` less the stacking. Nothing
is returned where the program opens no such span, or traces nothing in
the window."""
from bench import program_spans


def read(red: dict):
    return program_spans.ms_per_query(red, "regate.arch_workload")
