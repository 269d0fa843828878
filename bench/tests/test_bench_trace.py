"""The reduction from a device trace to per-layer numbers: idle share,
kernel and program time, top operations, idle gaps by host span."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH]

from harness import spec, tracing  # noqa: E402

DEV = "/device:TPU:0"
KIND = "TPU v5 lite"
REDUCE = ('%timeline_reduce.1 = (f32[4096,1]) custom-call(%a, %b, %c, %d, '
          '%e, %f), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={f32[4096,240]{1,0}, '
          'f32[4096,240]{1,0}, f32[4096,240]{1,0}, f32[4096,7,240]{2,1,0}, '
          'f32[1,240]{1,0}, f32[1,240]{1,0}}, metadata={op_name='
          '"jit(timeline_reduce)/pallas_call"}')


def ev(name, a, b, **stats):
    return tracing.Event(name, a, b, stats, "")


@pytest.fixture()
def trace(tmp_path):
    """A hand-built window of 10 s: device ops at [1, 3] (two overlapping),
    [5, 6] (a reducer kernel call of 1 ms inside); host spans around them."""
    t = tracing.Trace(
        ops={DEV: [ev("fusion.1", 1.0, 2.0, hlo_module="jit__run_chunks_dep"),
                   ev("fusion.2", 1.5, 3.0, hlo_module="jit__run_chunks_dep"),
                   ev("timeline_reduce.1", 5.0, 5.001, long_name=REDUCE,
                      hlo_module="jit__run_chunks_dep"),
                   ev("copy.3", 5.001, 6.0, hlo_module="jit_other")]},
        modules={DEV: [ev("jit__run_chunks_dep(1)", 1.0, 5.001)]},
        host=[ev(tracing.WINDOW_SPAN, 0.0, 10.0),
              ev("sweep.call", 0.2, 3.5), ev("sweep.call", 4.5, 9.0),
              ev("np.asarray", 6.5, 8.0)],
        window=(0.0, 10.0))
    path = str(tmp_path / "t.json.gz")
    tracing.export(t, path)
    return tracing.load(path)


def test_busy_and_idle(trace):
    assert tracing.busy_s(trace) == pytest.approx(3.0)
    assert tracing.idle_share(trace) == pytest.approx(70.0)


def test_idle_gaps_by_innermost_host_span(trace):
    gaps = dict(tracing.idle_gaps(trace))
    assert gaps == pytest.approx({"np.asarray": 4.0, "idle": 2.0,
                                  "sweep.call": 1.0})


def test_top_ops(trace):
    top = dict(tracing.top_ops(trace))
    assert top["fusion.2"] == pytest.approx(1.5)
    assert top["copy.3"] == pytest.approx(0.999)


def test_kernel_shapes_and_time(trace):
    (k,) = tracing.kernel_events(trace, "timeline_reduce")
    assert tracing.operand_shapes(k)[:4] == [(4096, 240)] * 3 + [
        (4096, 7, 240)]
    ctx = type("Ctx", (), {"trace": trace, "device_kind": KIND})()
    assert tracing.calls_in_window(trace, "sweep.call") == 2
    assert spec.reader("reduce_ms.sweep")(ctx) == pytest.approx(1e3 * 0.001
                                                                 / 2)
    xla = spec.reader("pipeline_xla_ms.sweep")(ctx)
    assert xla == pytest.approx(1e3 * 2.0 / 2)


def test_readers_find_nothing_without_their_kernel(trace):
    ctx = type("Ctx", (), {"trace": trace, "device_kind": KIND})()
    for name in ("propagation_ms.harden", "sample_ms.detect",
                 "ingest_ms.detect"):
        assert spec.reader(name)(ctx) is None, name


def test_ops_named_by_their_hlo_text():
    """On a TPU an op's event name is its instruction's HLO text and it may
    carry no ``hlo_module`` stat: the program is the ``XLA Modules`` event
    that contains it, the kernel is found in the text itself."""
    fusion = ("%fusion.127 = f32[4096,240]{1,0:T(8,128)} fusion(f32[4096]"
              "{0} %p.1), kind=kLoop, calls=%fused_computation.31")
    t = tracing.Trace(
        ops={DEV: [ev(fusion, 1.0, 1.5), ev(REDUCE, 1.5, 1.502),
                   ev("%copy.9 = f32[8]{0} copy(f32[8]{0} %x)", 3.0, 3.5)]},
        modules={DEV: [ev("jit__run_chunks_dep(7)", 0.9, 2.0),
                       ev("jit_other(3)", 2.9, 3.6)]},
        host=[ev(tracing.WINDOW_SPAN, 0.0, 10.0), ev("sweep.call", 0.5, 2.5)],
        window=(0.0, 10.0))
    top = dict(tracing.top_ops(t))
    assert top["fusion.127 f32[4096,240]"] == pytest.approx(0.5)
    assert top["timeline_reduce"] == pytest.approx(0.002)
    assert [e.name for e in tracing.program_ops(t, "run_chunks")] == [
        fusion, REDUCE]
    ctx = type("Ctx", (), {"trace": t, "device_kind": KIND})()
    assert spec.reader("pipeline_xla_ms.sweep")(ctx) == pytest.approx(500.0)
    assert spec.reader("reduce_ms.sweep")(ctx) == pytest.approx(2.0)


def test_an_op_reading_a_kernel_result_is_no_kernel():
    consumer = ev("%reduce.13 = f32[4096]{0} reduce(f32[4096,1]{1,0} "
                  "%pallas_call.63, f32[] %c), dimensions={1}, to_apply="
                  "%pallas_call.51.reduce_sub_computation", 0.0, 1.0)
    assert not tracing.is_kernel(consumer)
    assert tracing.is_kernel(ev(REDUCE, 0.0, 1.0))
    assert tracing.is_kernel(ev("fusion.3", 0.0, 1.0,
                                tf_op="jit(ingest_hist)/pallas_call"))


def test_nested_ops_count_once():
    """A ``while`` op spans its body's ops: the program's time outside its
    kernels is what the ops cover, less what the kernels cover."""
    loop = "%while.13 = (s32[]) while(s32[] %p), condition=%c, body=%b"
    body = "%fusion.125 = f32[4096,240]{1,0} fusion(f32[4096]{0} %x)"
    t = tracing.Trace(
        ops={DEV: [ev(loop, 1.0, 2.0), ev(body, 1.1, 1.5),
                   ev(REDUCE, 1.5, 1.9)]},
        modules={DEV: [ev("jit__run_chunks_dep(7)", 1.0, 2.0)]},
        host=[ev(tracing.WINDOW_SPAN, 0.0, 3.0), ev("sweep.call", 0.5, 2.5)],
        window=(0.0, 3.0))
    ctx = type("Ctx", (), {"trace": t, "device_kind": KIND})()
    assert spec.reader("pipeline_xla_ms.sweep")(ctx) == pytest.approx(600.0)
    assert tracing.covered_s(t.ops[DEV]) == pytest.approx(1.0)
    assert dict(tracing.top_ops(t)) == pytest.approx(
        {"timeline_reduce": 0.4, "fusion.125 f32[4096,240]": 0.4})


@pytest.mark.parametrize("metric,module,kernel,span,want_ms", [
    ("propagation_ms.harden", "jit_fixed_point_ell(3)",
     '%body.3 = s32[22016,1]{1,0} custom-call(s32[352256]{0} %a), '
     'custom_call_target="tpu_custom_call"', "harden.job", 400.0),
    ("ingest_ms.detect", "jit_ingest_hist(5)",
     '%ingest_hist.1 = s32[3776,128]{1,0} custom-call(s32[4014080]{0} '
     '%pad.0), custom_call_target="tpu_custom_call"', "detect.job", 200.0),
])
def test_kernel_time_readers(metric, module, kernel, span, want_ms):
    """Two kernel runs of 0.2 s each in one job, beside an XLA op: 0.4 s
    per job, 0.2 s per run."""
    t = tracing.Trace(
        ops={DEV: [ev(kernel, 1.0, 1.2), ev("%copy.1 = s32[8]{0} copy()",
                                            1.2, 1.3),
                   ev(kernel, 1.4, 1.6)]},
        modules={DEV: [ev(module, 0.9, 1.7)]},
        host=[ev(tracing.WINDOW_SPAN, 0.0, 3.0), ev(span, 0.5, 2.0)],
        window=(0.0, 3.0))
    ctx = type("Ctx", (), {"trace": t, "device_kind": KIND})()
    assert spec.reader(metric)(ctx) == pytest.approx(want_ms)
