"""The comparison that decides ``correct``: records against the reference.

``deviation`` walks two record lists in step and returns two numbers:
the largest relative deviation over the float fields, and the count of
everything that has to agree exactly (record counts, field sets,
labels, integers, fields named by an ``exact`` prefix, non-finite
values). A run is correct when the first is at most its limit and the
second is 0.

The relative deviation is ``|a - b| / max(floor, |a|, |b|)``. The sweep
records use the floor 1e-30; the program-plane records use 1.0, since
their ``gated_frac_absdiff_*`` fields are differences of near-equal
fractions, so an absolute floor keeps rounding in them from reading as
a relative error.
"""
from __future__ import annotations

import math


def deviation(ref: list[dict], got: list[dict], exact: tuple = (),
              floor: float = 1e-30) -> dict:
    """``{"max_rel_dev": float, "mismatches": int, "first": str}`` of
    ``got`` against ``ref``; ``first`` names the first mismatch."""
    worst = 0.0
    bad = abs(len(ref) - len(got))
    first = f"{len(got)} records, reference has {len(ref)}" if bad else ""
    for i, (a, b) in enumerate(zip(ref, got)):
        if a.keys() != b.keys():
            bad += 1
            first = first or (f"record {i}: fields differ: "
                              f"{sorted(a.keys() ^ b.keys())}")
            continue
        for k, va in a.items():
            vb = b[k]
            same = True
            if not isinstance(va, float) or k.startswith(exact):
                same = va == vb
            elif not (math.isfinite(va) and math.isfinite(vb)):
                same = va == vb or (math.isnan(va) and math.isnan(vb))
            else:
                worst = max(worst, abs(va - vb) / max(floor, abs(va),
                                                      abs(vb)))
            if not same:
                bad += 1
                first = first or f"record {i} {k}: {vb!r} != {va!r}"
    return {"max_rel_dev": worst, "mismatches": bad, "first": first}
