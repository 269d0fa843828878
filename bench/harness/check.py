"""Comparisons that decide ``correct``: the program's answers against the
plain references, each reduced to one number that is held to its limit."""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

# a float verdict agrees when it lies within this share of its column's
# scale (its largest finite magnitude in the sample): float32
# arithmetic over the failover model's operands (up to ~1e6 cores, ~7200 s)
# stays near 1e-6 of that scale, bfloat16 near 4e-3
FLOAT_TOL = 1e-4


def disagreement_shares(got: Dict[str, np.ndarray],
                        want: Dict[str, np.ndarray]) -> Dict[str, float]:
    """Per verdict key, the share of entries that disagree.  Booleans and
    integers must be equal; floats must match in their non-finite entries
    and lie within ``FLOAT_TOL`` of the column scale elsewhere.  A key the
    program did not return disagrees everywhere."""
    out = {}
    for k, ref in want.items():
        b = np.asarray(ref)
        if k not in got:
            out[k] = 1.0
            continue
        a = np.asarray(got[k])
        if a.shape != b.shape:
            out[k] = 1.0
            continue
        if b.size == 0:
            out[k] = 0.0
            continue
        if b.dtype.kind == "b" or a.dtype.kind in "biu":
            bad = a.astype(np.float64) != b.astype(np.float64)
        else:
            a = a.astype(np.float64)
            b = b.astype(np.float64)
            fin = np.isfinite(b)
            scale = np.abs(b[fin]).max() if fin.any() else 1.0
            bad = np.isfinite(a) != fin
            both = fin & np.isfinite(a)
            with np.errstate(invalid="ignore"):
                gap = np.abs(np.where(both, a - b, 0.0))
            bad |= gap > FLOAT_TOL * max(scale, 1e-30)
            bad |= ~fin & ~np.isfinite(a) & (a != b)
        out[k] = float(np.count_nonzero(bad)) / bad.size
    return out


def count_mismatches(got: np.ndarray, want: np.ndarray) -> int:
    """Entries that differ (shape mismatch counts every entry)."""
    a, b = np.asarray(got), np.asarray(want)
    if a.shape != b.shape:
        return int(max(a.size, b.size))
    return int(np.count_nonzero(a != b))


def line(items: List[Tuple[str, float, float]]) -> Dict:
    """The ``check`` entry of the result line: each number and its limit."""
    return {name: {"value": value, "limit": limit}
            for name, value, limit in items}


def passed(items: List[Tuple[str, float, float]]) -> bool:
    return all(value <= limit for _, value, limit in items)
