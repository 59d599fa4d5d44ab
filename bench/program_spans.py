"""The program's own spans in a traced run, and the counts they carry.

The program opens a host span at each layer boundary of its jax call
path (``bk.span`` of ``repro.core.backend``): names start with
``regate.``, children nest inside their caller's span on its thread,
and a span about a transfer or a kernel carries integer counts
(``arrays``, ``bytes``, ``rows``, ``e_max``, ``events``) as event stats.

``bench/trace.py``'s ``load`` keeps only the benchmark's ``bench.*``
annotations, so the readers of the program's spans take them from the
same ``.xplane.pb`` through ``of(red)``. It returns

* ``spans``: ``[name, start, end]`` of every ``regate.*`` event, on
  the profiler's clock (nanoseconds);
* ``counts``: ``[name, start, {stat: value}]`` of those that carry
  stats.

The trace is the newest ``.xplane.pb`` under ``ROOT``'s
``results/bench`` whose ``bench.query`` spans are the reduction's.
``ROOT`` is this checkout, where ``bench/run.py`` has the harness write
its traces; a harness run given another root writes them where these
readers do not look. A trace of a program without the hook holds no
``regate.*`` event, and the readers then return nothing.
"""
from __future__ import annotations

import functools
import glob
import os

from bench.trace import length, union

PREFIX = "regate."
QUERY = "bench.query"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=4)
def _load(path: str, mtime_ns: int) -> dict:
    return load(path)


def load(path: str) -> dict:
    """``queries`` (the ``bench.query`` spans), ``spans`` and ``counts``
    of the program's spans in one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    queries, spans, counts = [], [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == QUERY:
                    queries.append([name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns])
                elif name.startswith(PREFIX):
                    spans.append([name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns])
                    stats = dict(ev.stats)
                    if stats:
                        counts.append([name, ev.start_ns, stats])
    return {"queries": sorted(queries, key=lambda s: s[1]),
            "spans": sorted(spans, key=lambda s: s[1]),
            "counts": sorted(counts, key=lambda c: c[1])}


def of(red: dict):
    """The program's ``spans`` and ``counts`` in the run ``red`` was
    reduced from, or ``None`` where no trace of that run is found."""
    queries = [s for s in red["spans"] if s[0] == QUERY]
    if not queries:
        return None
    paths = glob.glob(os.path.join(ROOT, "results", "bench", "**",
                                   "*.xplane.pb"), recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        tr = _load(path, os.stat(path).st_mtime_ns)
        if tr["queries"] == queries:
            return tr
    return None


def ms_per_query(red: dict, name: str):
    """Milliseconds per query inside the spans called ``name``."""
    prog = of(red)
    if prog is None or not red["queries"]:
        return None
    spans = [s for s in prog["spans"] if s[0] == name]
    if not spans:
        return None
    return sum(e - s for _n, s, e in spans) / 1e6 / red["queries"]


def host_ms_per_query(red: dict, names, outside: str | None = None):
    """Host milliseconds per query inside the spans whose name is in
    ``names``: their time less the device-busy union inside them, and
    less the host time of the spans called ``outside`` nested in
    them."""
    prog = of(red)
    if prog is None or not red["queries"]:
        return None
    spans = [s for s in prog["spans"] if s[0] in names]
    if not spans:
        return None
    busy = next(iter(red["busy"].values()), [])

    def host(s, e):
        return (e - s) - length(union(busy, s, e))
    inner = [sp for sp in prog["spans"] if sp[0] == outside]
    ns = 0.0
    for _n, s, e in spans:
        ns += host(s, e) - sum(host(cs, ce) for _c, cs, ce in inner
                               if s <= cs and ce <= e)
    return ns / 1e6 / red["queries"]


def stats_of(red: dict, name: str) -> list[dict]:
    """The stats of every span called ``name``."""
    prog = of(red)
    if prog is None:
        return []
    return [st for n, _s, st in prog["counts"] if n == name]


def count_per_query(red: dict, name: str, stat: str):
    """The stat ``stat`` of the spans called ``name``, summed, per
    query."""
    stats = stats_of(red, name)
    if not stats or not red["queries"]:
        return None
    return sum(st[stat] for st in stats) / red["queries"]
