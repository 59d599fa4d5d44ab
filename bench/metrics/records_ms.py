"""records_ms: host milliseconds per query spent assembling the
records (``BatchResult.records`` or ``ProgramPlaneBatch.records``), from
the ``bench.records`` annotation."""


def read(red: dict):
    spans = [s for s in red["spans"] if s[0] == "bench.records"]
    if not spans or not red["queries"]:
        return None
    return sum(e - s for _n, s, e in spans) / 1e6 / red["queries"]
