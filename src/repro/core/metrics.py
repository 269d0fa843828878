"""Request availability through a failover window (paper §8, Fig 8).

Pulls together the fleet and orchestration models into the availability
the paper reports while a region fails over and back.
"""

from __future__ import annotations

import random
from typing import Dict, List, Tuple

from repro.core.omg import Orchestrator
from repro.core.service import ServiceSpec

BASELINE_AVAILABILITY = 0.9997   # ambient (paper Fig 8)


def availability_during_failover(fleet: Dict[str, ServiceSpec],
                                 orch: Orchestrator,
                                 n_samples: int = 48, seed: int = 3
                                 ) -> List[Tuple[float, float]]:
    """Core-flow availability sampled through the failover window.

    A critical request succeeds unless (a) ambient noise, (b) a fail-close
    dependency on a currently-down service fires, or (c) Always-On capacity
    is short (only if the orchestrator reported a shortfall).
    """
    rng = random.Random(seed)
    tl = orch.timeline
    out: List[Tuple[float, float]] = []
    unsafe = [(s, d) for s in fleet.values()
              if s.failure_class.survives_failover
              for d in s.unsafe_deps()]
    crit_cores = sum(s.cores for s in fleet.values()
                     if s.failure_class.survives_failover)
    if not tl.t:
        return [(0.0, BASELINE_AVAILABILITY)]
    t_end = tl.t[-1]
    rl_down_windows = []
    for i, t in enumerate(tl.t):
        down = tl.series.get("rl_not_bursted", [0] * len(tl.t))[i]
        rl_down_windows.append((t, down))

    # sample times are nondecreasing and rl_down_windows is time-ascending,
    # so one merged sweep replaces the per-sample rescan: advance a shared
    # pointer to the last window at or before t (O(n+m) total, same
    # last-match semantics as the scan it replaced)
    n_win = len(rl_down_windows)
    j = -1
    for i in range(n_samples):
        t = t_end * i / max(1, n_samples - 1)
        avail = BASELINE_AVAILABILITY + rng.gauss(0, 2e-5)
        # fail-close cascade: weight by affected caller cores
        while j + 1 < n_win and rl_down_windows[j + 1][0] <= t:
            j += 1
        down_now = rl_down_windows[j][1] if j >= 0 else 0.0
        if down_now > 0 and unsafe:
            affected = sum(s.cores for s, d in unsafe
                           if fleet.get(d) is not None
                           and fleet[d].failure_class.preemptible)
            avail -= 0.9 * affected / max(1.0, crit_cores)
        if orch.report is not None and not orch.report.always_on_ok:
            avail -= 0.05
        out.append((t, max(0.0, min(1.0, avail))))
    return out
