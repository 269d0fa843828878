"""A configuration names the function that builds its fleet and whether its
edge traffic is the paper's Table 2 or the fleet's own, so a deployment of
another shape (its own builder, edge weights, chain depth and fan-out)
comes into the benchmark as new files and entries, with no edit to the
harness."""

import copy
import json
import os
import shutil
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from harness import check as chk  # noqa: E402
from harness import fleet, jobs, spec  # noqa: E402
from harness import reference as ref  # noqa: E402

SEED = 2 ** 31 + 7654321          # a seed past 32 signed bits
CONFIGS = ("uber-paper-legacy", "uber-paper-hardened")


def _config(name: str) -> dict:
    return fleet.load(os.path.join(BENCH, "configs", name + ".json"))


def _same_columns(a, b):
    assert a.keys() == b.keys()
    for k in a:
        assert a[k].dtype == b[k].dtype and np.array_equal(a[k], b[k]), k


@pytest.fixture(scope="module")
def paper_fleet():
    return fleet.build(_config("uber-paper-legacy"))


# -- the builder -------------------------------------------------------------


@pytest.mark.parametrize("name", CONFIGS)
def test_default_builder_is_the_named_one(name):
    """No ``builder`` key builds what naming ``synthesize_fleet_state``
    builds, and (before any hardening) what ``synthesize_fleet`` builds."""
    from repro.core.service import synthesize_fleet
    config = _config(name)
    config["fleet"]["scale"] = 0.02
    named = dict(copy.deepcopy(config), builder=fleet.DEFAULT_BUILDER)
    assert "builder" not in config
    _same_columns(fleet.columns(fleet.build(config)),
                  fleet.columns(fleet.build(named)))
    legacy = dict(copy.deepcopy(config), state="legacy")
    _same_columns(fleet.columns(fleet.build(legacy)),
                  fleet.columns(synthesize_fleet(as_arrays=True,
                                                 **config["fleet"])))


def test_paper_fleet_counts(paper_fleet):
    c = fleet.columns(paper_fleet)
    assert len(c["tier"]) == 21_979
    assert len(c["src"]) == 120_827
    assert int(np.count_nonzero(~c["fail_open"])) == 249


def test_weight_column_is_the_table2_rule(paper_fleet):
    """The paper fleet's own edge weights, which the program samples with,
    are the reference's Table 2 rule bit for bit."""
    c = fleet.columns(paper_fleet)
    want = ref.edge_weights(c["tier"], c["src"], c["dst"])
    assert paper_fleet.edges.weight.dtype == want.dtype == np.float32
    assert np.array_equal(paper_fleet.edges.weight.view(np.uint32),
                          want.view(np.uint32))
    assert np.array_equal(c["weight"], want.astype(np.float64))


UNWEIGHTED = '''
from repro.core.fleet_state import synthesize_fleet_state


def build(**fleet):
    fs = synthesize_fleet_state(**fleet)
    fs.edges.weight = None
    return fs
'''

REWEIGHTED = '''
from repro.core.fleet_state import synthesize_fleet_state


def build(**fleet):
    """The paper fleet with its edge weights in reverse order."""
    fs = synthesize_fleet_state(**fleet)
    fs.edges.weight = fs.edges.weight[::-1].copy()
    return fs
'''


def _module(tmp_path, monkeypatch, name, text):
    (tmp_path / f"{name}.py").write_text(text)
    monkeypatch.syspath_prepend(str(tmp_path))
    return name


@pytest.mark.parametrize("change", [{"fleet": {"with_edges": False}},
                                    {"builder": "builtins.dict"},
                                    {"builder": "unweighted_fleet.build"}],
                         ids=["no-edges", "no-fleet", "no-weights"])
def test_builder_without_weighted_edges_is_refused(change, tmp_path,
                                                   monkeypatch):
    _module(tmp_path, monkeypatch, "unweighted_fleet", UNWEIGHTED)
    config = _config("uber-paper-legacy")
    config["name"] = "edgeless-fleet"
    config["fleet"]["scale"] = 0.02
    config["fleet"].update(change.get("fleet", {}))
    config.update({k: v for k, v in change.items() if k != "fleet"})
    with pytest.raises(TypeError, match="edgeless-fleet") as e:
        fleet.build(config)
    assert config.get("builder", fleet.DEFAULT_BUILDER) in str(e.value)


def test_unknown_edge_weights_is_refused():
    config = dict(_config("uber-paper-legacy"), name="odd-weights",
                  edge_weights="uniform")
    with pytest.raises(ValueError, match="odd-weights.*table2"):
        fleet.build(config)


# -- the detection check's edge weights --------------------------------------


@pytest.mark.parametrize("builder, edge_weights, passes", [
    (None, None, True),
    ("reweighted_fleet.build", None, False),
    ("reweighted_fleet.build", "builder", True)],
    ids=["paper", "reweighted-table2", "reweighted-own"])
def test_detect_check_reads_the_stated_weights(builder, edge_weights, passes,
                                               tmp_path, monkeypatch):
    """A configuration that states no weights carries Table 2 traffic, which
    the reference works out on its own: a builder that weights the edges
    otherwise fails the check, unless the configuration states its own."""
    _module(tmp_path, monkeypatch, "reweighted_fleet", REWEIGHTED)
    c = spec.find_cell(spec.load_benchmark(ROOT), "legacy.detect", ROOT)
    config = copy.deepcopy(c.config)
    config["fleet"]["scale"] = 0.02
    config.update({k: v for k, v in (("builder", builder),
                                     ("edge_weights", edge_weights)) if v})
    traffic = copy.deepcopy(c.traffic)
    traffic["chunk_records"] = 250_000
    job = jobs.make(config, traffic, 1, SEED)
    job.setup()
    job.warm()
    job.call(0)
    items = job.check()
    assert [name for name, _, _ in items] == ["edge_mismatches"]
    assert chk.passed(items) == passes, items


# -- a deployment of another shape, from new files alone ---------------------

HOPS, FAN_OUT = 10, 40

BUILDER = '''
"""A small fleet of another shape than the paper's: a fail-close relay
chain of critical services {hops} hops deep down to a preemptible one, a
critical hub that calls {fan_out} preemptible services (every fifth
fail-close), fail-open calls among those, and log-normal edge weights."""

import numpy as np

from repro.core.fleet_state import POOL_NONE, EdgeArrays, FleetState


def build(hops, fan_out, seed):
    rng = np.random.default_rng(seed)
    chain = list(range(hops))                  # critical, Always-On
    hub = hops                                 # critical, Active-Migrate
    leaf = hops + 1                            # preemptible end of the chain
    spokes = list(range(hops + 2, hops + 2 + fan_out))
    n = hops + 2 + fan_out
    tier = np.full(n, 4, np.int8)
    tier[chain] = 0
    tier[hub] = 1
    fclass = np.full(n, 2, np.int8)            # Restore-Later
    fclass[chain] = 0
    fclass[hub] = 1
    fclass[spokes[1::2]] = 3                   # Terminate
    src = chain + [hub] * fan_out + spokes[:-1]
    dst = chain[1:] + [leaf] + spokes + spokes[1:]
    fail_open = np.ones(len(src), bool)
    fail_open[:hops] = False
    fail_open[hops:hops + fan_out:5] = False
    cpr = rng.choice([0.5, 1.0, 2.0], n)
    replicas = rng.integers(1, 6, n).astype(np.int64)
    return FleetState(
        names=[f"svc-{{i:03d}}" for i in range(n)], tier=tier, fclass=fclass,
        cores_per_replica=cpr, replicas=replicas,
        replicas_live=replicas.copy(), placement=np.zeros(n, np.int8),
        pool=np.full(n, POOL_NONE, np.int8), locked=np.zeros(n, bool),
        traffic_enabled=np.ones(n, bool),
        edges=EdgeArrays(src=np.asarray(src, np.int32),
                         dst=np.asarray(dst, np.int32), fail_open=fail_open,
                         weight=rng.lognormal(0.0, 2.0, len(src))
                         .astype(np.float32)))
'''


def _snapshot(*dirs):
    out = {}
    for d in dirs:
        for base, subdirs, files in os.walk(d):
            subdirs[:] = [s for s in subdirs if s != "__pycache__"]
            for f in files:
                p = os.path.join(base, f)
                with open(p, "rb") as fh:
                    out[p] = fh.read()
    return out


@pytest.mark.parametrize("kernels", ["0", "1"], ids=["xla", "pallas"])
def test_new_shape_config_from_files_alone(kernels, tmp_path, monkeypatch):
    """Builder module, configuration, traffic mixes and a copy of
    BENCHMARK.json with the configuration and two cells added: the
    hardening and detection jobs check out against the shared reference,
    with the fleet's own edge weights and its ten-round fixed point, on the
    XLA and the Pallas (ELL) paths."""
    from repro.graph import CallGraph
    from repro.kernels.ufa.propagation import ell_from_csr

    monkeypatch.setenv("REPRO_UFA_KERNELS", kernels)
    before = _snapshot(os.path.join(BENCH, "harness"),
                       os.path.join(BENCH, "configs"))
    module = f"deep_chain_fleet_{kernels}"
    (tmp_path / f"{module}.py").write_text(
        BUILDER.format(hops=HOPS, fan_out=FAN_OUT))
    monkeypatch.syspath_prepend(str(tmp_path))
    config = {"name": "deep-chain", "state": "legacy",
              "builder": f"{module}.build", "edge_weights": "builder",
              "fleet": {"hops": HOPS, "fan_out": FAN_OUT, "seed": 11}}
    (tmp_path / "deep-chain.json").write_text(json.dumps(config))
    traffic_dir = tmp_path / "traffic"
    shutil.copytree(os.path.join(BENCH, "traffic"), traffic_dir)
    harden = json.loads((traffic_dir / "harden.json").read_text())
    harden["ensemble_scenarios"] = 16
    (traffic_dir / "harden-16.json").write_text(json.dumps(harden))
    detect = json.loads((traffic_dir / "detect.json").read_text())
    detect.update(records_per_edge=40, chunk_records=1024)
    (traffic_dir / "detect-small.json").write_text(json.dumps(detect))

    bench = json.loads(json.dumps(spec.load_benchmark(ROOT)))
    bench["configs"].append({
        "name": "deep-chain", "source": "test fleet",
        "file": str(tmp_path / "deep-chain.json"), "reduced": [],
        "why": "ten-hop fail-close chain, fan-out 40"})
    cells = {"deep-chain.harden": ("harden-16", "harden_s"),
             "deep-chain.detect": ("detect-small", "detect_records_per_s")}
    for cell, (mix, metric) in cells.items():
        bench["workloads"].append({"name": cell, "config": "deep-chain",
                                   "traffic": mix, "chips": 1, "why": cell})
        for m in bench["end_to_end"]:
            if m["name"] == metric:
                m["workloads"].append(cell)

    got = {}
    for cell in cells:
        c = spec.find_cell(bench, cell, ROOT, traffic_dir=str(traffic_dir))
        assert {m["name"] for m in c.end_to_end} == {"setup_s",
                                                     cells[cell][1]}
        job = jobs.make(c.config, c.traffic, c.chips, SEED)
        job.setup()
        job.warm()
        for i in range(2):
            job.call(i)
        got[c.traffic["job"]] = job, job.check()

    job, items = got["harden"]
    assert items == [("graph_mismatches", 0, 0)]
    c = job.cols
    n = len(c["tier"])
    _, rounds = ref.fixed_point(n, c["src"], c["dst"], ~c["fail_open"],
                                (c["fclass"] >= ref.RL)[None, :])
    # breakage climbs one hop a round; the last round changes nothing
    assert rounds == HOPS + 1
    assert all(k["cert_rounds"] == rounds for k in job.kept)
    g = CallGraph.from_fleet_state(fleet.build(job.config))
    assert ell_from_csr(g.n, g.indptr, g.dst, ~g.fail_open)[0].shape[1] == 40

    job, items = got["detect"]
    assert items == [("edge_mismatches", 0, 0)]
    c = job.cols
    assert not np.allclose(c["weight"],
                           ref.edge_weights(c["tier"], c["src"], c["dst"]))
    # sampled by the Table 2 rule instead, the reference counts otherwise
    job.config = dict(job.config, edge_weights="table2")
    assert job.check()[0][1] > 0

    assert _snapshot(os.path.join(BENCH, "harness"),
                     os.path.join(BENCH, "configs")) == before
