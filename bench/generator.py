"""The one query generator: a traffic file's parameters and a seed in,
the cell's queries out.

A query is a plain dict that names everything the entry point and the
reference need, and nothing built by the program:

* ``workloads``: one entry per deployment shape of the configuration
  (``name``, ``shape``, ``kind``, ``seq_len``, ``global_batch``,
  ``n_chips``, ``tp``). With ``batch_fractions`` in the traffic file
  each query draws every shape's global batch as one of those fractions
  of the published batch; the op count of the trace does not depend on
  the batch, so every query has the same compiled shapes.
* ``npus``, ``policies``: as the traffic file lists them.
* ``axes``: the knob axes. An axis is either fixed ``values`` or ``n``
  values drawn per query, distinct and sorted, uniform (``scale``
  ``linear``) or log-uniform (``log``) in ``[lo, hi]``. Only the values
  change from query to query, never the number of unique (sa_width,
  delay_scale, window_scale) triples.

Query ``i`` of seed ``s`` is drawn from its own stream ``(s, i)``, so a
run's queries do not depend on how many of them fit in its window.
"""
from __future__ import annotations

import math

import numpy as np

# the canonical knob order of a sweep record: sa_width outermost, then
# window_scale, delay_scale, leak_off_logic, leak_sram_sleep, and
# leak_sram_off innermost
KNOB_ORDER = ("sa_width", "window_scale", "delay_scale", "leak_off_logic",
              "leak_sram_sleep", "leak_sram_off")


def stream(seed: int, *key: int) -> np.random.Generator:
    """The random stream of ``key`` under ``seed`` (any whole number)."""
    return np.random.default_rng([seed & (2 ** 64 - 1), *key])


def _axis(spec, rng: np.random.Generator) -> list:
    if "values" in spec:
        return list(spec["values"])
    lo, hi, n = spec["lo"], spec["hi"], spec["n"]
    if spec.get("scale", "linear") == "log":
        vals = np.exp(rng.uniform(math.log(lo), math.log(hi), n))
    else:
        vals = rng.uniform(lo, hi, n)
    vals = sorted(float(v) for v in vals)
    if len(set(vals)) != n:
        raise ValueError(f"axis draw {vals} repeats a value")
    return vals


def query(config: dict, traffic: dict, seed: int, i: int) -> dict:
    """Query ``i`` of the cell (configuration x traffic) under ``seed``."""
    rng = stream(seed, i)
    fracs = traffic.get("batch_fractions")
    workloads = []
    for shape in config["shapes"]:
        sh = config["shapes"][shape]
        frac = float(rng.choice(fracs)) if fracs else 1.0
        workloads.append({
            "name": f"{config['arch']['name']}-{shape}", "shape": shape,
            "kind": sh["kind"], "seq_len": sh["seq_len"],
            "global_batch": max(1, int(sh["global_batch"] * frac)),
            "n_chips": sh["n_chips"], "tp": sh["tp"]})
    axes = {name: _axis(spec, rng)
            for name, spec in traffic["knobs"].items()}
    return {"index": i, "workloads": workloads,
            "npus": list(traffic["npus"]),
            "policies": list(traffic.get("policies", ())), "axes": axes}


def knob_points(axes: dict) -> list[dict]:
    """The query's knob grid as the entry points cross it, one dict of
    the six knob columns per point, in the canonical order."""
    full = {k: axes.get(k, [None] if k.startswith(("leak", "sa_"))
                        else [1.0]) for k in KNOB_ORDER}
    pts = [{}]
    for k in KNOB_ORDER:
        pts = [dict(p, **{k: v}) for p in pts for v in full[k]]
    return pts

