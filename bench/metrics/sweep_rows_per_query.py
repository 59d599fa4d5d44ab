"""sweep_rows_per_query: op rows the sweep kernel evaluates per query,
the ``ops`` count (the stacked trace's rows) that the program's
``regate.harvest`` span carries for each sweep-kernel call, summed over
the calls. Nothing is returned where the program counts no such rows."""
from bench import program_spans


def read(red: dict):
    stats = [st for st in program_spans.stats_of(red, "regate.harvest")
             if "ops" in st]
    if not stats or not red["queries"]:
        return None
    return sum(st["ops"] for st in stats) / red["queries"]
