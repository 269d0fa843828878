"""Every cell's job driven through the functions the CLI uses, on a tiny
fleet on the CPU: the comparison with the plain references passes on the
system as it is, fails for the lower-precision control, and fails for each
fault planted in the timed path.  The CLI itself refuses to run without a
TPU."""

import copy
import os
import subprocess
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from harness import cell as cell_mod  # noqa: E402
from harness import jobs, spec  # noqa: E402

SEED = 2 ** 31 + 1234567          # past 32 signed bits, as the driver's are
CELLS = [w["name"] for w in spec.load_benchmark(ROOT)["workloads"]]


def tiny(name: str) -> spec.Cell:
    """The cell on a 2% fleet, on one device, with small calls."""
    cell = spec.find_cell(spec.load_benchmark(ROOT), name, ROOT)
    cell = copy.deepcopy(cell)
    cell.config["fleet"]["scale"] = 0.02
    cell.chips = 1
    t = cell.traffic
    if t["job"] == "sweep":
        t["scenarios_per_call"] = 256
        t["check"]["rows_per_call"] = 16
    elif t["job"] == "detect":
        t["chunk_records"] = 250_000
    else:
        t["ensemble_scenarios"] = 64
    return cell


def run(cell, seconds=0.3):
    return cell_mod.run(cell, SEED, seconds, False, time.perf_counter(),
                        log=open(os.devnull, "w"))


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct(name):
    cell = tiny(name)
    r = run(cell)
    assert r["correct"], r["check"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "check" and r["check"]
    assert r["device"]["platform"] == "cpu"


def _job(name, calls=2):
    cell = tiny(name)
    job = jobs.make(cell.config, cell.traffic, 1, SEED)
    job.setup()
    job.warm()
    for i in range(calls):
        job.call(i)
    return job


@pytest.mark.parametrize("name", ["hardened.sweep-64k", "legacy.detect",
                                  "legacy.harden"])
def test_control_fails(name):
    """The reference at the next lower precision (bfloat16 floats and
    counts), or with propagation cut to one sweep, in the program's place."""
    import ml_dtypes
    job = _job(name)
    from harness import check as chk
    assert chk.passed(job.check())
    assert not chk.passed(job.check(control=ml_dtypes.bfloat16))


# -- faults planted in the timed path ---------------------------------------


def _sweep_fault(kind):
    from repro.core import sweep_engine
    orig = sweep_engine.SweepEngine.run
    first = {}

    def run(self, grid=None, *a, **kw):
        n = len(next(iter(grid.values())))
        if kind == "half":
            out = orig(self, {k: v[: n // 2] for k, v in grid.items()},
                       *a, **kw)
            return {k: np.resize(v, (n,) + v.shape[1:])
                    for k, v in out.items()}
        out = orig(self, grid, *a, **kw)
        if kind == "stale":
            return first.setdefault("out", out)
        if kind == "altered":
            out["t_availability_mean"] = out["t_availability_mean"] * 0.999
        return out
    return sweep_engine.SweepEngine, "run", run


def _detect_fault(kind):
    from repro.core import dependency
    orig = dependency.RuntimeFailCloseDetector.ingest_batch
    seen = [0]

    def ingest_batch(self, edge_id, failed, errored):
        seen[0] += 1
        if kind == "stale" and seen[0] % 2 == 0:
            return None
        if kind == "half":
            h = len(edge_id) // 2
            return orig(self, edge_id[:h], failed[:h], errored[:h])
        orig(self, edge_id, failed, errored)
        if kind == "altered":
            self.calls[0] += 1
    return dependency.RuntimeFailCloseDetector, "ingest_batch", ingest_batch


def _harden_fault(kind):
    from repro.graph import propagation
    orig = propagation.fixed_point

    def fixed_point(dark, consts):
        if kind == "stale":
            return dark, orig(dark, consts)[1]
        broken, rounds = orig(dark, consts)
        s = broken.shape[0]
        if kind == "half" and s > 1:
            broken = broken.at[s // 2:].set(broken[: s - s // 2])
        if kind == "altered":
            rounds = rounds + 1
        return broken, rounds
    return propagation, "fixed_point", fixed_point


FAULTS = {"sweep": (_sweep_fault, ("stale", "half", "altered")),
          "detect": (_detect_fault, ("stale", "half", "altered")),
          "harden": (_harden_fault, ("stale", "half", "altered"))}
CASES = [(name, kind) for name in CELLS
         for kind in FAULTS[tiny(name).traffic["job"]][1]]


@pytest.mark.parametrize("name,kind", CASES)
def test_fault_makes_run_incorrect(name, kind, monkeypatch):
    cell = tiny(name)
    target, attr, fn = FAULTS[cell.traffic["job"]][0](kind)
    monkeypatch.setattr(target, attr, fn)
    if cell.traffic["job"] == "harden":
        from repro.graph import planner
        monkeypatch.setattr(planner, "fixed_point", fn)
    r = run(cell)
    assert not r["correct"], (kind, r["check"])


# -- the command line --------------------------------------------------------


def _cli(cwd, workload="legacy.harden"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_without_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_cli_fails_without_the_system(tmp_path):
    """A checkout of only BENCHMARK.json and the benchmark's files."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr
