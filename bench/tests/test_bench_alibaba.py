"""The trace-shaped fleet (Alibaba 2021 microservice traces) as a
configuration: it builds from its file through its named builder, keeps the
shape its file states, and its hardening job checks out against the shared
reference on the XLA and the Pallas (split ELL layout) paths; the readers of
the propagation layout's counts read them from a recorded trace."""

import copy
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from harness import fleet, jobs, spec, tracing  # noqa: E402
from harness import reference as ref  # noqa: E402

SEED = 2 ** 31 + 7654321          # a seed past 32 signed bits
CONFIG = "alibaba-2021-legacy"
CELL = "alibaba-legacy.harden"


def _config(name: str) -> dict:
    return fleet.load(os.path.join(BENCH, "configs", name + ".json"))


@pytest.fixture(scope="module")
def small():
    """The configuration from its file, at a tenth of its scale."""
    config = _config(CONFIG)
    config["fleet"]["scale"] = 0.1
    return config, fleet.build(config)


def test_config_builds_from_its_file(small):
    """Its named builder, its own edge weights; the paper fleet's services,
    tiers, cores and classes at the same scale; the same fleet every
    build."""
    config, fs = small
    assert config["builder"] == "repro.core.fleet_shapes.alibaba_fleet_state"
    assert config["edge_weights"] == "builder" and config["state"] == "legacy"
    paper = _config("uber-paper-legacy")
    paper["fleet"]["scale"] = 0.1
    got, want = fleet.columns(fs), fleet.columns(fleet.build(paper))
    for k in ("tier", "fclass", "cores"):
        assert np.array_equal(got[k], want[k]), k
    again = fleet.columns(fleet.build(config))
    for k, v in got.items():
        assert v.dtype == again[k].dtype and np.array_equal(v, again[k]), k


def test_shape_holds(small):
    """Mean out-degree near 6, a hub at least eight times the layout's base
    width, callees of fan-in 100 or more, one edge per caller and callee,
    weights skewed within the Table 2 cells whose volume they keep, and
    fail-close relays several hops deep."""
    from repro.kernels.ufa.propagation import ell_width
    _, fs = small
    c = fleet.columns(fs)
    n = len(c["tier"])
    deg = np.bincount(c["src"], minlength=n)
    assert abs(deg.mean() - 6.0) <= 0.5
    assert ell_width(deg) <= 32 and deg.max() >= 8 * ell_width(deg)
    assert np.bincount(c["dst"], minlength=n).max() >= 100
    assert len(np.unique(c["src"] * n + c["dst"])) == len(c["src"])
    assert not (c["src"] == c["dst"]).any()
    w = np.sort(c["weight"])[::-1]
    assert w[:len(w) // 100].sum() > 0.5 * w.sum()
    cell = c["tier"][c["src"]] * ref.N_TIERS + c["tier"][c["dst"]]
    vol = np.bincount(cell, c["weight"], minlength=ref.N_TIERS ** 2)
    used = np.bincount(cell, minlength=ref.N_TIERS ** 2) > 0
    assert np.allclose(vol[used], ref.TABLE2.ravel()[used], rtol=1e-4)
    _, rounds = ref.fixed_point(n, c["src"], c["dst"], ~c["fail_open"],
                                (c["fclass"] >= ref.RL)[None, :])
    assert rounds >= 4


@pytest.mark.parametrize("kernels", ["0", "1"], ids=["xla", "pallas"])
def test_harden_check(kernels, small, monkeypatch):
    """The cell's job on the fleet checks out against the shared reference
    on both propagation paths; the Pallas path walks a split layout."""
    from repro.graph.propagation import edge_consts
    monkeypatch.setenv("REPRO_UFA_KERNELS", kernels)
    config, _ = small
    c = spec.find_cell(spec.load_benchmark(ROOT), CELL, ROOT)
    assert c.config["name"] == CONFIG and c.chips == 1
    assert c.traffic["job"] == "harden"
    traffic = copy.deepcopy(c.traffic)
    traffic["ensemble_scenarios"] = 64
    job = jobs.make(config, traffic, 1, SEED)
    job.setup()
    assert ("ell_tag" in edge_consts(job.graph)) == (kernels == "1")
    job.warm()
    for i in range(2):
        job.call(i)
    assert job.check() == [("graph_mismatches", 0, 0)]
    assert all(k["cert_rounds"] >= 4 and k["certified"] for k in job.kept)


# -- the readers of the layout's counts --------------------------------------

DEV = "/device:TPU:0"


def ev(name, a, b, **stats):
    return tracing.Event(name, a, b, stats, "python")


def layout_trace(counted=True):
    """Two hardening jobs; each certifies (5 rounds) and runs an ensemble
    (6 rounds) over a layout of 1,000 edges in 1,900 slot loads a round.
    ``counted=False``: the spans of a program that counts none of it."""
    counts = {"edges": 1000, "slots": 1900, "split_rows": 12}
    certify = dict(counts, rounds=5) if counted else {}
    ensemble = dict(counts, rounds=6) if counted else {}
    host = [ev(tracing.WINDOW_SPAN, 0.0, 10.0),
            ev("harden.job", 0.0, 4.0), ev("harden.job", 5.0, 9.0)]
    for a in (0.0, 5.0):
        host += [ev("ufa.graph.certify", a + 0.1, a + 0.5, services=50,
                    **certify),
                 ev("ufa.graph.ensemble", a + 0.6, a + 1.0, scenarios=256,
                    **ensemble)]
    host.append(ev("ufa.graph.certify", 12.0, 13.0, rounds=99, edges=1,
                   slots=1))                           # outside the window
    return tracing.Trace(ops={DEV: [ev("fusion.1", 1.5, 2.0)]},
                         modules={DEV: []}, host=host, window=(0.0, 10.0))


@pytest.mark.parametrize("metric,want", [
    ("prop_slot_fill.harden", 100.0 * 1000 / 1900),
    ("prop_rounds.harden", 5 + 6)])
def test_layout_count_readers(metric, want, tmp_path):
    path = str(tmp_path / "t.json.gz")
    tracing.export(layout_trace(), path)
    read = spec.reader(metric)
    ctx = type("Ctx", (), {"trace": tracing.load(path), "job": None})()
    assert read(ctx) == pytest.approx(want)
    # a program whose spans carry no counts, and one with no spans at all
    ctx.trace = layout_trace(counted=False)
    assert read(ctx) is None
    ctx.trace.host = [e for e in ctx.trace.host
                      if not e.name.startswith("ufa.")]
    assert read(ctx) is None


def test_new_cell_reports_the_harden_metrics():
    bench = spec.load_benchmark(ROOT)
    cell = spec.find_cell(bench, CELL, ROOT)
    assert {m["name"] for m in cell.end_to_end} == {"harden_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "device_idle.harden", "propagation_ms.harden",
        "planner_host_ms.harden", "planner_rounds.harden",
        "prop_slot_fill.harden", "prop_rounds.harden"}
    paper = spec.find_cell(bench, "legacy.harden", ROOT)
    assert {m["name"] for m in paper.per_layer} == {
        m["name"] for m in cell.per_layer}
