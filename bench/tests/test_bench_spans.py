"""The readers of the program's own host spans (``ufa.*``) and named device
scopes, on hand-built traces: per-call division, idle time inside a span,
call time outside the program's span, scope attribution by op text, and
nothing read where the program marks nothing."""

import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH]

from harness import spans, spec, tracing  # noqa: E402

DEV = "/device:TPU:0"
MAIN = "python"
PROG = "jit(_run_chunks_dep)/while/body"
KERNEL = ', custom_call_target="tpu_custom_call"'


def ev(name, a, b, thread=MAIN, **stats):
    return tracing.Event(name, a, b, stats, thread)


# instruction name -> op_name metadata of the compiled sweep program; on
# the chip an op's event is its instruction's text without the metadata
NAMES = {}


def op(hlo, a, b, op_name, kernel=False):
    NAMES[hlo] = op_name
    return ev(f"%{hlo} = f32[4096]{{0}} fusion(%p)" + (KERNEL if kernel
                                                       else ""), a, b, DEV)


def sweep_trace():
    """Two calls in a 10 s window; the device runs one pipeline program in
    [1, 2] during the first: propagation kernel 0.2 s and gathers 0.1 s
    (dependency), 0.2 s analytic, 0.3 s timeline series and a 0.1 s reducer
    kernel, and a 0.1 s output write under no scope, all inside the
    ``while`` of the chunk loop."""
    dep = "jit(_run_chunks_dep)/ufa_dependency"
    NAMES["while.3"] = "jit(_run_chunks_dep)/while"
    ops = [ev("%while.3 = (s32[]) while(s32[] %p), condition=%c, body=%b",
              1.0, 2.0, DEV),
           op("propagation_round.3", 1.0, 1.2, dep + "/jit(fixed_point_ell)"
              "/while/body/propagation_round/pallas_call", kernel=True),
           op("fusion.1", 1.2, 1.3, dep + "/gather"),
           op("fusion.2", 1.3, 1.5, PROG + "/closed_call/ufa_analytic/mul"),
           op("fusion.3", 1.5, 1.8, PROG + "/closed_call/ufa_timeline/add"),
           op("timeline_reduce.1", 1.8, 1.9, PROG + "/closed_call/"
              "ufa_timeline/jit(timeline_reduce)/timeline_reduce/pallas_call",
              kernel=True),
           op("dynamic-update-slice.4", 1.9, 2.0,
              PROG + "/dynamic_update_slice")]
    host = [ev(tracing.WINDOW_SPAN, 0.0, 10.0)]
    for call, run, prep, disp, fetch in [
            ((0.5, 3.0), (0.8, 2.5), (0.8, 0.9), (0.9, 1.0), (1.5, 2.5)),
            ((4.0, 6.0), (4.2, 5.8), (4.2, 4.4), (4.4, 4.5), (5.0, 5.8))]:
        host += [ev("sweep.call", *call), ev("ufa.sweep.run", *run),
                 ev("ufa.sweep.prepare", *prep),
                 ev("ufa.sweep.dispatch", *disp),
                 ev("ufa.sweep.fetch", *fetch)]
    host.append(ev("ufa.sweep.run", 0.0, 20.0, thread="other"))  # outside
    return tracing.Trace(ops={DEV: ops},
                         modules={DEV: [ev("jit__run_chunks_dep(3)", 1.0,
                                           2.0, DEV)]},
                         host=host, window=(0.0, 10.0))


def detect_trace():
    host = [ev(tracing.WINDOW_SPAN, 0.0, 10.0),
            ev("detect.job", 0.0, 4.0), ev("detect.job", 5.0, 9.0),
            ev("ufa.detect.run", 0.0, 4.0), ev("ufa.detect.run", 5.0, 9.0),
            ev("ufa.detect.tables", 0.1, 0.4),
            ev("ufa.detect.tables", 5.1, 5.2),
            ev("ufa.detect.verdicts", 3.5, 3.9),
            ev("ufa.detect.verdicts", 8.5, 8.7)]
    return tracing.Trace(ops={DEV: [op("fusion.1", 1.0, 2.0, "jit(f)/x")]},
                         modules={DEV: []}, host=host, window=(0.0, 10.0))


def harden_trace():
    """Two jobs; the planner runs in [1, 3] (three rounds, the device busy
    in [1.5, 2]) and in [6, 8] (two rounds, the device idle)."""
    host = [ev(tracing.WINDOW_SPAN, 0.0, 10.0),
            ev("harden.job", 0.0, 4.0), ev("harden.job", 5.0, 9.0),
            ev("ufa.planner.plan", 1.0, 3.0), ev("ufa.planner.plan", 6.0, 8.0)]
    host += [ev("ufa.planner.round", a, a + 0.5)
             for a in (1.0, 1.6, 2.2, 6.0, 7.0)]
    return tracing.Trace(ops={DEV: [op("body.1", 1.5, 2.0, "jit(f)/x")]},
                         modules={DEV: []}, host=host, window=(0.0, 10.0))


def unmarked(t):
    """The same trace from a program that marks nothing: no ``ufa.*`` host
    spans (and, in ``NAMES``, no ``ufa_*`` scope)."""
    return tracing.Trace(
        ops=t.ops, modules=t.modules,
        host=[e for e in t.host if not e.name.startswith("ufa.")],
        window=t.window)


CASES = [
    # (metric, trace, value): every value is per call of the cell
    ("prepare_ms.sweep", sweep_trace, 1e3 * (0.1 + 0.2) / 2),
    # device busy until 2.0: 0.5 s of the first fetch, all of the second
    ("fetch_ms.sweep", sweep_trace, 1e3 * (0.5 + 0.8) / 2),
    ("client_ms.sweep", sweep_trace, 1e3 * ((2.5 - 1.7) + (2.0 - 1.6)) / 2),
    ("analytic_ms.sweep", sweep_trace, 1e3 * 0.2 / 2),
    ("timeline_xla_ms.sweep", sweep_trace, 1e3 * 0.3 / 2),
    ("dep_stage_ms.sweep", sweep_trace, 1e3 * (0.2 + 0.1) / 2),
    ("tables_ms.detect", detect_trace, 1e3 * (0.3 + 0.1) / 2),
    ("verdicts_ms.detect", detect_trace, 1e3 * (0.4 + 0.2) / 2),
    ("planner_host_ms.harden", harden_trace, 1e3 * (1.5 + 2.0) / 2),
    ("planner_rounds.harden", harden_trace, 5 / 2),
]


@pytest.fixture()
def names(monkeypatch):
    """The sweep program's op names, as ``pipeline_op_names`` would read
    them from the compiled program of the context's job."""
    sweep_trace()
    found = dict(NAMES)
    monkeypatch.setattr(spans, "pipeline_op_names", lambda job: found)
    return found


@pytest.mark.parametrize("metric,make,want", CASES,
                         ids=[c[0] for c in CASES])
def test_span_and_scope_readers(metric, make, want, names):
    read = spec.reader(metric)
    ctx = type("Ctx", (), {"trace": make(), "job": None})()
    assert read(ctx) == pytest.approx(want)
    ctx.trace = unmarked(ctx.trace)
    names.update({k: re.sub(r"ufa_\w+/", "", v) for k, v in names.items()})
    assert read(ctx) is None


@pytest.mark.parametrize("metric", ["analytic_ms.sweep",
                                    "timeline_xla_ms.sweep",
                                    "dep_stage_ms.sweep"])
def test_scope_readers_refuse_names_of_another_program(metric, names):
    """A traced op that the compiled text does not name means the names
    came from another executable: the reader raises, and the harness leaves
    the metric out, rather than charge ops to the wrong stage."""
    del names["fusion.2"]
    ctx = type("Ctx", (), {"trace": sweep_trace(), "job": None})()
    with pytest.raises(LookupError, match=r"fusion\.2"):
        spec.reader(metric)(ctx)


def test_op_names_from_the_compiled_program():
    """The names come from the compiled program's text; the chunk loop's
    write of a stage's result takes that stage's name; a job with no sweep
    engine has none."""
    text = (
        'ENTRY %main {\n'
        '  %fusion.2 = f32[4096]{0} fusion(%p), kind=kLoop, '
        'metadata={op_name="jit(f)/while/body/ufa_analytic/mul" '
        'stack_frame_id=3}, backend_config={}\n'
        '  ROOT %copy.4 = f32[4096]{0} copy(%fusion.2), '
        'metadata={op_name="jit(f)/ufa_timeline/x"}\n'
        '  %custom-call = f32[16]{0} custom-call(), '
        'custom_call_target="AllocateBuffer"\n'
        '  %fusion.5 = (f32[4096]{0}, pred[4096]{0}) fusion(%p, %q), '
        'kind=kLoop, metadata={op_name="jit(f)/while/body/ufa_timeline/y"}\n'
        '  %get-tuple-element.6 = f32[4096]{0} get-tuple-element(%fusion.5), '
        'index=0\n'
        '  %dynamic_update_slice.7 = f32[16,4096]{1,0} dynamic-update-slice('
        '%p.1, %get-tuple-element.6, %i, /*index=3*/%c), '
        'metadata={op_name="jit(f)/while/body/dynamic_update_slice"}\n'
        '  %dynamic_update_slice.8 = f32[16,4096]{1,0} dynamic-update-slice('
        '%p.2, %p.3, %i, %c), '
        'metadata={op_name="jit(f)/while/body/dynamic_update_slice"}\n}')

    class Compiled:
        def as_text(self):
            return text

    class Engine:
        def _pipeline(self, grid):
            assert grid == "grid 0"
            fn = type("Fn", (), {"lower": lambda self, *a, **k: type(
                "Lowered", (), {"compile": lambda self: Compiled()})()})()
            return fn, (), {}

    job = type("Job", (), {"engine": Engine(),
                           "grid": lambda self, i: f"grid {i}"})()
    assert spans.pipeline_op_names(job) == {
        "fusion.2": "jit(f)/while/body/ufa_analytic/mul",
        "copy.4": "jit(f)/ufa_timeline/x", "custom-call": "",
        "fusion.5": "jit(f)/while/body/ufa_timeline/y",
        "get-tuple-element.6": "",
        "dynamic_update_slice.7": "jit(f)/while/body/ufa_timeline/y",
        "dynamic_update_slice.8": "jit(f)/while/body/dynamic_update_slice"}
    assert spans.pipeline_op_names(object()) == {}


def test_idle_by_span_charges_the_innermost_span():
    got = dict(spans.idle_by_span(sweep_trace(), "sweep.call"))
    assert got == pytest.approx({
        spans.BETWEEN: 0.5 + 1.0 + 4.0,
        spans.CLIENT: 0.3 + 0.5 + 0.2 + 0.2,
        "ufa.sweep.prepare": 0.1 + 0.2,
        "ufa.sweep.dispatch": 0.1 + 0.1,
        "ufa.sweep.run": 0.5,
        "ufa.sweep.fetch": 0.5 + 0.8})
    assert sum(got.values()) == pytest.approx(9.0)


def test_device_time_under_no_scope(names):
    t = sweep_trace()
    scopes = ("ufa_dependency", "ufa_analytic", "ufa_timeline")
    assert spans.unscoped_ms(t, names, r"run_chunks", scopes,
                             "sweep.call") == pytest.approx(1e3 * 0.1 / 2)
    # the pipeline's time outside its kernels: the analytic and timeline
    # stages, the dependency stage less its kernel, and the unscoped ops
    ctx = type("Ctx", (), {"trace": t, "job": None})()
    read = lambda m: spec.reader(m)(ctx)
    assert read("pipeline_xla_ms.sweep") == pytest.approx(
        read("analytic_ms.sweep") + read("timeline_xla_ms.sweep")
        + read("dep_stage_ms.sweep") - 1e3 * 0.2 / 2 + 1e3 * 0.1 / 2)


def test_scope_tool_refuses_without_tpu():
    import subprocess
    root = os.path.dirname(BENCH)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "bench/tools/scope_coverage.py", "--workload",
         "hardened.sweep-64k", "--seed", str(2 ** 31 + 1234567),
         "--seconds", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
