"""opgen_ms: host milliseconds per query spent tracing the query's
workloads (``opgen.arch_workload``) and stacking them
(``opgen.stack_traces``), from the ``bench.opgen`` annotation. Only an
entry that traces fresh workloads in every query opens that span."""


def read(red: dict):
    spans = [s for s in red["spans"] if s[0] == "bench.opgen"]
    if not spans or not red["queries"]:
        return None
    return sum(e - s for _n, s, e in spans) / 1e6 / red["queries"]
