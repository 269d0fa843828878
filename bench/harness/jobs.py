"""The kinds of work a cell's window drives, one class per ``job`` key of a
traffic mix.  Each job builds its configuration in ``setup``, compiles
every shape its calls use in ``warm``, serves one call in ``call`` (through
the system's public entry points, with its answers on the host when it
returns), and afterwards compares a seeded sample of those answers with the
plain references in ``check``."""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from harness import check as chk
from harness import fleet as fleet_mod
from harness import reference as ref
from harness import traffic as tr

# scenario axes the program fills with its operating point when a traffic
# mix leaves them out
DEFAULT_AXES = {"traffic_mult": 2.0, "burst_delay_s": ref.PREHEAT_S,
                "burst_availability": 1.0, "cloud_quota_frac": 1.0,
                "overcommit_factor": 1.5, "evict_fraction": 1.0}

Items = List[Tuple[str, float, float]]


class Job:
    """Shared plumbing: the cell's configuration, traffic, seed and limits."""

    span = "call"          # host span around each call in a traced window
    unit = "calls"

    def __init__(self, config: Dict, traffic: Dict, chips: int, seed: int):
        self.config, self.traffic = config, traffic
        self.chips, self.seed = chips, int(seed)
        self.limits = traffic["check"]["limits"]

    def _build(self):
        """The configuration's fleet; its columns go to ``self.cols``."""
        fs = fleet_mod.build(self.config)
        self.cols = fleet_mod.columns(fs)
        return fs

    def describe(self) -> str:
        c = self.cols
        return (f"{self.config['name']}: {len(c['tier'])} services, "
                f"{len(c['src'])} edges, "
                f"{int(np.count_nonzero(~c['fail_open']))} fail-close")

    def release(self):
        """Drop the program's state before the references run."""


# ---------------------------------------------------------------------------
# fused sweep
# ---------------------------------------------------------------------------


class SweepJob(Job):
    """``SweepEngine.run`` on a fresh scenario grid per call."""

    span = "sweep.call"
    unit = "scenarios"

    def setup(self):
        from repro.core.capacity import RegionCapacity
        from repro.core.omg import Orchestrator
        from repro.graph import CallGraph

        t = self.traffic
        fs = self._build()
        orch = Orchestrator(fs, RegionCapacity.for_fleet(
            self.config["name"], fs), scale=1.0)
        self.ts = (np.arange(t["steps"], dtype=np.float64)
                   * (t["horizon_s"] / t["steps"]))
        self.engine_seed = tr.small_seed(self.seed, "sweep/engine")
        self.engine = orch.sweep_engine(
            graph=CallGraph.from_fleet_state(fs), seed=self.engine_seed,
            ts=self.ts, devices=self.chips)
        self.n = int(t["scenarios_per_call"])
        self.kept = []

    def grid(self, call: int) -> Dict[str, np.ndarray]:
        return tr.scenario_grid(self.traffic["axes"], self.n, self.seed, call)

    def warm(self):
        self.engine.run(self.grid(-1))

    def call(self, i: int) -> float:
        grid = self.grid(i)
        out = self.engine.run(grid)
        rows = tr.sample_rows(self.n, self.traffic["check"]["rows_per_call"],
                              self.seed, i)
        self.kept.append(({k: v[rows] for k, v in grid.items()},
                          {k: np.asarray(v)[rows] for k, v in out.items()}))
        return float(self.n)

    def release(self):
        self.engine = None

    # -- check ----------------------------------------------------------
    def _reference(self, grid: Dict, dt) -> Dict[str, np.ndarray]:
        c = self.cols
        g = {k: np.asarray(grid.get(k, np.full(len(next(iter(grid.values()))),
                                               v)), np.float64)
             for k, v in DEFAULT_AXES.items()}
        # dependency stage: one shared uniform per service, scenario s
        # darkens the preemptibles below its eviction fraction
        n = len(c["tier"])
        pre, crit = c["fclass"] >= ref.RL, c["fclass"] <= ref.AM
        u = np.random.default_rng(self.engine_seed).random(n)
        uniq, inv = np.unique(g["evict_fraction"], return_inverse=True)
        dark = (u[None, :] < uniq[:, None]) & pre[None, :]
        broken, _ = ref.fixed_point(n, c["src"], c["dst"], ~c["fail_open"],
                                    dark)
        n_bc = (broken & crit[None, :]).sum(axis=1)[inv]
        g["dep_broken_frac"] = n_bc / max(1, int(crit.sum()))
        g["storm_refrac"] = np.zeros_like(g["traffic_mult"])
        g["storm_broken_frac"] = np.zeros_like(g["traffic_mult"])
        out = ref.analytic(ref.fleet_aggregates(c["fclass"], c["cores"]), g,
                           dt)
        tl = ref.timeline(ref.timeline_config(c["tier"], c["fclass"],
                                              c["cores"]), g, self.ts, dt)
        out.update({"t_" + k: v for k, v in tl.items()})
        out["dep_n_broken_critical"] = n_bc
        out["dep_n_dark"] = dark.sum(axis=1)[inv]
        return out

    def check(self, control=None) -> Items:
        """Every call's sampled rows against the float64 reference; with
        ``control`` (a dtype) the reference at that precision stands in for
        the program."""
        grids = {k: np.concatenate([g[k] for g, _ in self.kept])
                 for k in self.kept[0][0]}
        want = self._reference(grids, np.float64)
        if control is None:
            got = {k: np.concatenate([o[k] for _, o in self.kept])
                   for k in self.kept[0][1]}
        else:
            got = self._reference(grids, control)
        share = max(chk.disagreement_shares(got, want).values())
        return [("verdict_disagree_share", share,
                 self.limits["verdict_disagree_share"])]


# ---------------------------------------------------------------------------
# runtime fail-close detection
# ---------------------------------------------------------------------------


class DetectJob(Job):
    """``runtime_analysis`` over a fresh sampled stream per job."""

    span = "detect.job"
    unit = "records"

    def setup(self):
        self.fs = self._build()
        t = self.traffic
        self.n_records = int(t["records_per_edge"]) * len(self.cols["src"])
        self.chunk = int(t["chunk_records"])
        self.kept = []

    def _run(self, seed: int):
        from repro.core.dependency import runtime_analysis
        return runtime_analysis(self.fs, n_records=self.n_records, seed=seed,
                                chunk_records=self.chunk)

    def warm(self):
        self._run(tr.small_seed(self.seed, "detect/warm"))

    def call(self, i: int) -> float:
        s = tr.small_seed(self.seed, "detect/job", i)
        res = self._run(s)
        det, g = res["detector"], res["graph"]
        mask = np.zeros(len(g.fail_open), bool)
        mask[g.input_order] = ~g.fail_open
        self.kept.append((s, np.stack([det.calls, det.callee_failures,
                                       det.errors_given_failure,
                                       det.errors_given_ok], axis=1), mask))
        return float(res["n_records"])

    def release(self):
        self.fs = None

    # -- check ----------------------------------------------------------
    def reference_counts(self, seed: int, control=None) -> np.ndarray:
        """The job's stream drawn again from its seed (the same random bits,
        ``rbg`` keys split per chunk) and counted with ``np.bincount``.
        ``control``: per-chunk counts accumulated in that dtype."""
        import jax
        import jax.numpy as jnp

        c = self.cols
        unsafe = ~c["fail_open"]
        # Table 2 traffic is worked out here on its own; a deployment that
        # states its own is sampled with the fleet's weights
        if self.config.get("edge_weights", "table2") == "table2":
            weight = ref.edge_weights(c["tier"], c["src"], c["dst"])
        else:
            weight = c["weight"]
        prob, alias, _ = ref.sampling_tables(weight, unsafe, seed)
        n_chunks = max(1, -(-self.n_records // self.chunk))
        keys = jax.random.split(jax.random.key(seed, impl="rbg"), n_chunks)
        total = np.zeros((len(prob), 4),
                         np.int64 if control is None else control)
        done = 0
        for k in range(n_chunks):
            n = min(self.chunk, self.n_records - done)
            done += n
            bits = np.asarray(jax.random.bits(keys[k], (4, n), jnp.uint32))
            counts = ref.stream_counts(bits, prob, alias, unsafe)
            total = (total + counts).astype(total.dtype)
        return total.astype(np.float64)

    def check(self, control=None) -> Items:
        picks = tr.rng(self.seed, "check/jobs").choice(
            len(self.kept), min(len(self.kept),
                                self.traffic["check"]["jobs"]), replace=False)
        bad = 0
        for j in sorted(picks.tolist()):
            seed, det, mask = self.kept[j]
            want = self.reference_counts(seed)
            if control is not None:
                got4 = self.reference_counts(seed, control)
                got_mask, _ = ref.detect_mask(got4)
            else:
                calls, fails, err_f, err_ok = det.T
                got4 = np.stack([calls - fails - err_ok, err_ok,
                                 fails - err_f, err_f], axis=1)
                got_mask = mask
            want_mask, tie = ref.detect_mask(want)
            bad += int(np.count_nonzero(
                (np.asarray(got4, np.float64) != want).any(axis=1)
                | ((got_mask != want_mask) & ~tie)))
        return [("edge_mismatches", bad, self.limits["edge_mismatches"])]


# ---------------------------------------------------------------------------
# certification, ensemble and hardening
# ---------------------------------------------------------------------------


class HardenJob(Job):
    """``certify``, ``blackhole_ensemble`` and ``plan_hardening`` per job."""

    span = "harden.job"
    unit = "jobs"

    def setup(self):
        from repro.graph import CallGraph
        fs = self._build()
        self.graph = CallGraph.from_fleet_state(fs)
        self.kept = []

    def _run(self, seed: int):
        from repro.graph import blackhole_ensemble, certify, plan_hardening
        t = self.traffic
        cert = certify(self.graph)
        ens = blackhole_ensemble(self.graph, n_scenarios=t["ensemble_scenarios"],
                                 seed=seed)
        plan = plan_hardening(self.graph, batch=t["batch"])
        return cert, ens, plan

    def warm(self):
        self._run(tr.small_seed(self.seed, "harden/warm"))

    def call(self, i: int) -> float:
        s = tr.small_seed(self.seed, "harden/job", i)
        cert, ens, plan = self._run(s)
        g = self.graph
        self.kept.append({
            "seed": s, "cert_broken": cert.broken, "cert_rounds":
            int(cert.rounds),
            "ens": {k: np.asarray(ens[k]) for k in
                    ("n_dark", "n_broken", "n_broken_critical", "ok",
                     "rounds")},
            "hardened": g.input_edge_indices(plan.hardened_edges)
            if plan.hardened_edges else np.zeros(0, np.int64),
            "trajectory": [dict(r) for r in plan.trajectory],
            "certified": bool(plan.certified)})
        return 1.0

    def release(self):
        self.graph = None

    # -- check ----------------------------------------------------------
    def _answers(self, seed: int, hardened: np.ndarray, trajectory,
                 max_rounds: int) -> Dict:
        """The reference's answers for one job; the plan is checked by what
        it says: each trajectory entry's broken-critical count after its
        hardened prefix, and the certification of the whole plan."""
        c = self.cols
        n = len(c["tier"])
        pre, crit = c["fclass"] >= ref.RL, c["fclass"] <= ref.AM
        closed = ~c["fail_open"]
        fp = lambda cl, dark: ref.fixed_point(n, c["src"], c["dst"], cl,
                                              dark, max_rounds)
        broken, rounds = fp(closed, pre[None, :])
        out = {"cert_broken": broken[0], "cert_rounds": rounds}
        fractions, dark = ref.blackhole_draws(
            n, pre, seed, self.traffic["ensemble_scenarios"])
        b, r = fp(closed, dark)
        bc = b & crit[None, :]
        out["ens"] = {"n_dark": dark.sum(axis=1), "n_broken": b.sum(axis=1),
                      "n_broken_critical": bc.sum(axis=1),
                      "ok": ~bc.any(axis=1), "rounds": np.int32(r)}
        traj = []
        for entry in trajectory:
            cl = closed.copy()
            cl[hardened[:entry["n_hardened"]]] = False
            bb, _ = fp(cl, pre[None, :])
            traj.append(int((bb[0] & crit & ~pre).sum()))
        cl = closed.copy()
        cl[hardened] = False
        bb, _ = fp(cl, pre[None, :])
        out["trajectory"] = traj
        out["certified"] = not bool((bb[0] & crit & ~pre).any())
        return out

    def check(self, control=None) -> Items:
        picks = tr.rng(self.seed, "check/jobs").choice(
            len(self.kept), min(len(self.kept),
                                self.traffic["check"]["jobs"]), replace=False)
        closed = ~self.cols["fail_open"]
        bad = 0
        for j in sorted(picks.tolist()):
            k = self.kept[j]
            h = np.asarray(k["hardened"], np.int64)
            want = self._answers(k["seed"], h, k["trajectory"], 0)
            if control is None:
                got = {"cert_broken": k["cert_broken"],
                       "cert_rounds": k["cert_rounds"], "ens": k["ens"],
                       "trajectory": [e["n_broken_critical"]
                                      for e in k["trajectory"]],
                       "certified": k["certified"]}
                # every hardened edge is a distinct fail-close edge
                bad += len(h) - len(np.unique(h))
                bad += int(np.count_nonzero(~closed[h]))
            else:
                got = self._answers(k["seed"], h, k["trajectory"], 1)
            bad += chk.count_mismatches(got["cert_broken"],
                                        want["cert_broken"])
            bad += int(got["cert_rounds"] != want["cert_rounds"])
            for key, v in want["ens"].items():
                bad += chk.count_mismatches(got["ens"][key], v)
            bad += chk.count_mismatches(np.asarray(got["trajectory"]),
                                        np.asarray(want["trajectory"]))
            bad += int(got["certified"] != want["certified"])
            bad += int(not want["certified"])
        return [("graph_mismatches", bad, self.limits["graph_mismatches"])]


KINDS = {"sweep": SweepJob, "detect": DetectJob, "harden": HardenJob}


def make(config: Dict, traffic: Dict, chips: int, seed: int) -> Job:
    return KINDS[traffic["job"]](config, traffic, chips, seed)
