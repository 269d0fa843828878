"""The general traffic generator: every draw of a run comes from the run's
``--seed`` and a stream name, so the same seed gives the same inputs and
each call of a window gets inputs of its own.

A traffic mix is a JSON file under ``bench/traffic/`` (see its ``job`` key
for the kind of work).  Scenario axes are given as ``{"uniform": [lo,
hi]}``, ``{"choice": [v, ...]}`` or ``{"value": v}``.
"""

from __future__ import annotations

import json
import os
import zlib
from typing import Dict

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")


def load(name: str, directory: str = TRAFFIC_DIR) -> Dict:
    with open(os.path.join(directory, name + ".json")) as f:
        return json.load(f)


def rng(seed: int, stream: str, index: int = 0) -> np.random.Generator:
    """An independent generator for (seed, stream, index)."""
    return np.random.default_rng(
        [int(seed) & 0xFFFFFFFFFFFFFFFF, zlib.crc32(stream.encode()),
         int(index) & 0xFFFFFFFF])


def small_seed(seed: int, stream: str, index: int = 0) -> int:
    """A seed in [0, 2**31) for APIs that take a 32-bit integer seed."""
    return int(rng(seed, stream, index).integers(0, 2 ** 31 - 1))


def scenario_grid(axes: Dict, n: int, seed: int, call: int
                  ) -> Dict[str, np.ndarray]:
    """One call's ``n`` scenarios: each axis drawn independently from its
    own stream of (seed, call), in float64."""
    out = {}
    for i, (key, spec) in enumerate(sorted(axes.items())):
        g = rng(seed, "grid/" + key, call)
        if "uniform" in spec:
            lo, hi = spec["uniform"]
            out[key] = g.uniform(lo, hi, n)
        elif "choice" in spec:
            out[key] = g.choice(np.asarray(spec["choice"], np.float64), n)
        else:
            out[key] = np.full(n, float(spec["value"]))
    return out


def sample_rows(n: int, k: int, seed: int, call: int) -> np.ndarray:
    """``k`` distinct rows of an ``n``-row answer, drawn from (seed, call),
    sorted."""
    k = min(k, n)
    return np.sort(rng(seed, "check/rows", call).choice(n, k, replace=False))
