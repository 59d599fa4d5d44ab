"""trace_ops_per_query: op rows the program traced per query, the
``ops`` count that each ``regate.arch_workload`` span carries, summed
over the spans: a query's stack before the sweep. Nothing is returned
where the program counts no such rows."""
from bench import program_spans


def read(red: dict):
    return program_spans.count_per_query(red, "regate.arch_workload", "ops")
