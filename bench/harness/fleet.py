"""Configurations: a fleet (deployment) built through the system's own
synthesis and hardening paths, from the sizes in its configuration file.

A configuration file may name, besides its ``fleet`` sizes and ``state``:

  ``builder``       the dotted path of the function that builds its fleet,
                    called with the ``fleet`` keys as keyword arguments; it
                    returns a ``FleetState`` with weighted call edges
                    (default ``repro.core.fleet_state.synthesize_fleet_state``);
  ``edge_weights``  what the detection reference samples with: ``table2``
                    (default), the paper's Table 2 rule, worked out by the
                    reference on its own, so a builder that weights its
                    edges otherwise fails the check; ``builder``, the
                    fleet's own weights (its ``weight`` column), for a
                    deployment whose traffic is not Table 2's;

so a deployment of another shape comes in as new files and entries.
"""

from __future__ import annotations

import importlib
import json
from typing import Dict

import numpy as np

DEFAULT_BUILDER = "repro.core.fleet_state.synthesize_fleet_state"
EDGE_WEIGHTS = ("table2", "builder")


def load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def build(config: Dict):
    """Build the configuration's fleet (``FleetState``) with its builder
    and bring it to the configuration's state.  ``legacy``: as built.
    ``hardened``: the paper's Table 5 classes, and the fail-close edges the
    hardening planner picks on the built graph set fail-open."""
    from repro.core.fleet_state import FleetState

    if config.get("edge_weights", "table2") not in EDGE_WEIGHTS:
        raise ValueError(f"configuration {config['name']!r}: edge_weights "
                         f"is one of {EDGE_WEIGHTS}")
    path = config.get("builder", DEFAULT_BUILDER)
    module, _, name = path.rpartition(".")
    fs = getattr(importlib.import_module(module), name)(**config["fleet"])
    if not (isinstance(fs, FleetState) and fs.edges is not None
            and fs.edges.n > 0 and fs.edges.weight is not None):
        raise TypeError(f"configuration {config['name']!r}: builder {path} "
                        "returned no FleetState with weighted call edges "
                        f"(got {type(fs).__name__})")
    state = config["state"]
    if state == "hardened":
        from repro.graph import CallGraph, plan_hardening
        graph = CallGraph.from_fleet_state(fs)
        plan = plan_hardening(graph, batch=config["hardening_batch"])
        if not plan.certified:
            raise RuntimeError("hardening planner did not certify the fleet")
        fs.edges.fail_open[graph.input_edge_indices(plan.hardened_edges)] = True
        fs.apply_ufa_target_classes()
    elif state != "legacy":
        raise ValueError(f"unknown fleet state {state!r}")
    return fs


def columns(fs) -> Dict[str, np.ndarray]:
    """The fleet's own columns, copied: what the plain references read.
    ``weight`` is each edge's RPC volume in float64, as the program samples
    it."""
    e = fs.edges
    return {"tier": np.array(fs.tier, np.int64),
            "fclass": np.array(fs.fclass, np.int64),
            "cores": np.asarray(fs.cores_per_replica, np.float64)
            * np.asarray(fs.replicas, np.float64),
            "src": np.array(e.src, np.int64), "dst": np.array(e.dst, np.int64),
            "fail_open": np.array(e.fail_open, bool),
            "weight": np.array(e.weight, np.float64)}
