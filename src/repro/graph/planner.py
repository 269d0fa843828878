"""Greedy hardening planner + dependency regression gate (paper §5-6).

The paper hardened 4,000+ unsafe dependencies before the 2x buffer could be
dropped, then gated deployments so new fail-close edges onto critical paths
never ship.  ``plan_hardening`` reproduces the first process: repeatedly
certify the fleet (multi-hop blackhole propagation), rank the fail-close
edges still carrying breakage by the *blast radius* of their caller (how
many critical services break when that caller breaks — exact, via the
batched kernel), convert the worst offenders to fail-open, and stop as
soon as the fleet certifies.  The recorded trajectory (cumulative edges
hardened vs. broken critical services) is the paper's hardening-count
curve.  ``regression_gate`` reproduces the second: diff two graphs and
fail on any new unsafe edge whose failure can reach a critical service.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.graph.callgraph import CallGraph
from repro.graph.propagation import (blast_radius, certify, edge_consts,
                                     fixed_point, harden_consts,
                                     radius_counts, radius_width)


@dataclasses.dataclass
class HardeningPlan:
    graph: CallGraph                       # final (hardened) graph
    hardened_edges: List[int]              # CSR edge indices, in plan order
    hardened_edge_names: List[Tuple[str, str]]
    trajectory: List[Dict[str, int]]       # per round: hardened so far,
                                           # broken criticals remaining
    certified: bool
    rounds: int

    @property
    def n_hardened(self) -> int:
        return len(self.hardened_edges)


def plan_hardening(graph: CallGraph, batch: int = 64,
                   max_rounds: int = 10_000,
                   service_weights=None) -> HardeningPlan:
    """Greedy multi-hop hardening until the fleet certifies.

    Each round: propagate the full preemption blackhole; the *frontier* is
    every fail-close edge whose callee is broken (these are the edges
    actually relaying failure).  Rank frontier edges by the blast radius of
    their caller — the exact number of critical services saved if this
    caller stops breaking — with RPC volume as the tie-break, harden the
    top ``batch``, repeat.  Terminates because every round converts >= 1
    fail-close edge and certification needs only finitely many.

    ``service_weights`` (optional (n,) float array) switches the frontier
    ranking to the *weighted* blast radius: each broken service counts
    its weight instead of 1.  The capacity optimizer feeds its
    availability-sensitivity weights here (``repro.optim.capacity
    .hardening_weights``) so the plan spends its first rounds on the
    edges whose breakage costs the most availability at the optimized
    operating point.  Certification and termination are still judged on
    the unweighted broken-critical count — only the greedy order changes;
    ``None`` keeps the historical ranking bit-identical.

    The loop stays on the host, so each round's radius batch is as wide as
    its frontier needs, but everything of size n or E stays on the
    device: the edge consts, the dark set and the fail-close mask, which
    is updated there.  A round is four jitted dispatches — the (1, n)
    fixed point (the program certify runs), its verdict (the
    broken-critical count and which of the plan's F fail-close edges form
    the frontier), the radius batch of the frontier's callers, and the
    mask update of the round's picks — and what crosses is a count, (F,)
    frontier bools, (width,) caller indices and counts, and (batch,)
    picks.  Ranking stays on the host in float64, so the plan is that of
    the historical host loop, edge for edge.
    """
    with obs.span("ufa.planner.plan", batch=batch):
        return _plan_hardening(graph, batch, max_rounds, service_weights)


@jax.jit
def _frontier(broken: jnp.ndarray, closed: jnp.ndarray,
              crit_live: jnp.ndarray, cand: jnp.ndarray,
              cand_dst: jnp.ndarray, cand_live: jnp.ndarray):
    """One round's verdict from its (1, n) fixed point ``broken``, on the
    device: the count of broken live criticals, and which fail-close edges
    ``cand`` form the frontier — still ``closed``, callee broken, caller
    live (``cand_live``: hardening an edge whose caller is itself dark
    changes nothing)."""
    return (jnp.count_nonzero(broken[0] & crit_live),
            closed[cand] & broken[0, cand_dst] & cand_live)


def _plan_hardening(graph: CallGraph, batch: int, max_rounds: int,
                    service_weights) -> HardeningPlan:
    dark = np.asarray(graph.preemptible, bool)
    consts = edge_consts(graph)                # backend-dispatched kernel
    # every frontier edge is fail-close at the start; padded to whole
    # buckets with repeats, whose verdicts the host drops
    cand = np.flatnonzero(~graph.fail_open).astype(np.int32)
    cand_pad = np.resize(cand, radius_width(len(cand)))
    cand_d = jnp.asarray(cand_pad)
    cand_dst_d = jnp.asarray(graph.dst[cand_pad])
    cand_live_d = jnp.asarray(~dark[graph.src[cand_pad]])
    crit_live_d = jnp.asarray(graph.critical & ~dark)
    crit_d = jnp.asarray(graph.critical)
    weights_d = (None if service_weights is None
                 else jnp.asarray(np.asarray(service_weights, np.float32)))
    dark_d = jnp.asarray(dark[None, :])
    hardened: List[int] = []
    trajectory: List[Dict[str, int]] = []
    rounds = 0
    certified = False
    while rounds < max_rounds:
        with obs.span("ufa.planner.round") as round_span:
            broken_d, _ = fixed_point(dark_d, consts)
            n_bc, on_frontier = jax.device_get(_frontier(
                broken_d, consts["closed"], crit_live_d, cand_d, cand_dst_d,
                cand_live_d))
            moved = n_bc.nbytes + on_frontier.nbytes
            n_bc = int(n_bc)
            round_span.set(broken_critical=n_bc, width=0, moved_bytes=moved)
            trajectory.append({"n_hardened": len(hardened),
                               "n_broken_critical": n_bc})
            obs.set_gauge("ufa_planner_broken_critical", n_bc)
            if n_bc == 0:
                certified = True
                break
            rounds += 1
            obs.inc("ufa_planner_rounds_total")
            frontier = cand[on_frontier[:len(cand)]]
            if len(frontier) == 0:
                # a bare assert here vanished under ``python -O``, leaving
                # the loop re-certifying the same stale state until
                # max_rounds — fail loudly instead (mirrors
                # EventLoop.max_events)
                raise RuntimeError(
                    "plan_hardening stalled: "
                    f"{n_bc} broken critical service(s) after "
                    f"{len(hardened)} hardened edge(s) but no fail-close "
                    "frontier edge relays the breakage into a live caller "
                    "— the propagation verdicts and the edge mask "
                    "disagree (inconsistent graph state?); hardening "
                    "cannot make progress")
            callers = np.unique(graph.src[frontier])
            counts = radius_counts(callers, consts, crit_d,
                                   weights=weights_d)
            radius = np.zeros(graph.n, counts.dtype)
            radius[callers] = counts
            score = radius[graph.src[frontier]].astype(np.float64)
            # tie-break on traffic volume (normalized to < 1 so it never
            # outranks a whole extra critical service)
            w = graph.weight[frontier].astype(np.float64)
            score += w / (w.max() + 1.0)
            pick = frontier[np.argsort(-score, kind="stable")[:batch]]
            hardened.extend(int(i) for i in pick)
            # padded to ``batch`` with repeats, so the update compiles once
            picks = np.resize(pick, batch)
            consts = harden_consts(consts, picks)
            width = radius_width(len(callers))
            # int32 caller indices up and their counts down, picks up
            moved += width * (4 + counts.itemsize) + picks.nbytes
            round_span.set(frontier=len(frontier), picked=len(pick),
                           width=width, moved_bytes=moved)
            obs.inc("ufa_planner_hardened_edges_total", int(len(pick)))
    g = graph.harden(hardened)
    if not certified:
        # ran out of rounds after a harden — the last cert is stale
        certified = certify(g, dark).ok
    return HardeningPlan(
        graph=g, hardened_edges=hardened,
        hardened_edge_names=g.edge_names(hardened),
        trajectory=trajectory, certified=certified, rounds=rounds)


# ---------------------------------------------------------------------------
# regression gate
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GateResult:
    ok: bool
    new_unsafe_edges: List[Tuple[str, str]]        # all newly-unsafe edges
    violations: List[Tuple[str, str, int]]         # those reaching critical
                                                   # services (+ blast count)

    def __bool__(self) -> bool:
        return self.ok


def regression_gate(baseline: CallGraph, candidate: CallGraph) -> GateResult:
    """Fail if the candidate graph introduces a fail-close edge that can
    reach a critical service — the per-deployment check that keeps the
    hardened fleet hardened.

    An edge (u -> v) "reaches a critical service" iff u, or any transitive
    fail-close caller of u, is critical: if v ever goes dark, that whole
    set breaks.  Computed exactly by darkening each new edge's *caller*
    alone and counting broken criticals (one batched propagation).  Edges
    are diffed by (caller, callee) name, so the two graphs may differ in
    shape (new services, re-ordered rows).
    """
    base_unsafe = baseline.unsafe_edge_keys()
    cand_unsafe_idx = np.flatnonzero(~candidate.fail_open)
    new_idx = [int(i) for i in cand_unsafe_idx
               if (candidate.names[candidate.src[i]],
                   candidate.names[candidate.dst[i]]) not in base_unsafe]
    new_edges = candidate.edge_names(new_idx)
    if not new_idx:
        obs.inc("ufa_gate_checks_total", verdict="ok")
        obs.set_gauge("ufa_gate_violations", 0)
        return GateResult(ok=True, new_unsafe_edges=[], violations=[])
    callers = np.unique(candidate.src[np.asarray(new_idx, np.int64)])
    radius = blast_radius(candidate, sources=callers)
    violations = [(c, d, int(radius[candidate.index[c]]))
                  for (c, d) in new_edges
                  if radius[candidate.index[c]] > 0]
    obs.inc("ufa_gate_checks_total",
            verdict="ok" if not violations else "fail")
    obs.set_gauge("ufa_gate_violations", len(violations))
    return GateResult(ok=not violations, new_unsafe_edges=new_edges,
                      violations=violations)
