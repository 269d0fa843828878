"""Configurations: a fleet (deployment) built through the system's own
synthesis and hardening paths, from the sizes in its configuration file."""

from __future__ import annotations

import json
from typing import Dict

import numpy as np


def load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def build(config: Dict):
    """Synthesize the configuration's fleet (``FleetState``) and bring it
    to the configuration's state.  ``legacy``: as synthesized.
    ``hardened``: the paper's Table 5 classes, and the fail-close edges the
    hardening planner picks on the synthesized graph set fail-open."""
    from repro.core.service import synthesize_fleet

    f = config["fleet"]
    fs = synthesize_fleet(
        scale=f["scale"], seed=f["seed"],
        unsafe_fraction=f["unsafe_fraction"], mean_deps=f["mean_deps"],
        demand_fraction=f["demand_fraction"],
        unsafe_chain_fraction=f["unsafe_chain_fraction"], as_arrays=True)
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
    """The fleet's own columns, copied: what the plain references read."""
    e = fs.edges
    return {"tier": np.array(fs.tier, np.int64),
            "fclass": np.array(fs.fclass, np.int64),
            "cores": np.asarray(fs.cores_per_replica, np.float64)
            * np.asarray(fs.replicas, np.float64),
            "src": np.array(e.src, np.int64), "dst": np.array(e.dst, np.int64),
            "fail_open": np.array(e.fail_open, bool)}
