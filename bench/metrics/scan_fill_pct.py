"""scan_fill_pct: per cent of the event-scan kernel's padded work that
is real events: 100 x the ``events`` over the ``rows`` x ``e_max`` of
the program's ``regate.scan_kernel`` spans, summed over the queries.
Every row is scanned for ``e_max`` steps, so the rest is padding.
Nothing is returned where the kernel did not run."""
from bench import program_spans


def read(red: dict):
    stats = program_spans.stats_of(red, "regate.scan_kernel")
    padded = sum(st["rows"] * st["e_max"] for st in stats)
    if not padded:
        return None
    return 100.0 * sum(st["events"] for st in stats) / padded
