"""call_host_ms: milliseconds per query of the entry-point call
(``bench.call``) during which no operation ran on the device: host
columns, transfers, dispatch, harvest and the host-side folds."""
from bench.trace import length, union


def read(red: dict):
    spans = [s for s in red["spans"] if s[0] == "bench.call"]
    if not spans or not red["queries"] or not red["busy"]:
        return None
    busy = next(iter(red["busy"].values()))
    host = 0.0
    for _n, s, e in spans:
        host += (e - s) - length(union(busy, s, e))
    return host / 1e6 / red["queries"]
