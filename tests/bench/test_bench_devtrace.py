"""The trace reduction on a small hand-built trace: device busy time, idle
gaps by host span, the op breakdown, kernel shapes, roofline share and the
peaks table."""
import types

import pytest

from bench import devtrace, layers
from bench.harness import load_module

#: a custom call's op event name as a v5e trace gives it: the op's HLO text
#: (256 rows of 16 KiB pages)
SENSE_HLO = ('%mlc_sense.1 = u32[256,4096]{1,0:T(8,128)S(1)} custom-call('
             'f32[8]{0:T(128)} %constant.45, f32[256,131072]{1,0:T(8,128)} '
             '%group_vth_3_.1), custom_call_target="tpu_custom_call", '
             'operand_layout_constraints={f32[8]{0}, f32[256,131072]{1,0}}, '
             'frontend_attributes={kernel_metadata={}}')
#: the stats a v5e trace gives each op event
TPU_STATS = {"device_offset_ps": 0, "device_duration_ps": 0,
             "Time Scale Multiplier": 1.0}
SENSE_BYTES = 8 * 4 + 256 * 131072 * 4 + 256 * 4096 * 4


def ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def plane(name, **lines):
    return types.SimpleNamespace(name=name, stats=[], lines=[
        types.SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


@pytest.fixture
def trace():
    host = plane("/host:CPU", python=[
        ev("bench.window", 1000, 10000),
        ev("bench.submit", 1000, 2000),
        ev("bench.step", 1500, 1000),        # inside the submit
        ev("bench.wait", 4000, 6000),
        ev("unrelated", 1000, 10000)])
    device = plane(
        "/device:TPU:0",
        XLA_Ops=[ev(SENSE_HLO, 3000, 2000, **TPU_STATS),
                 ev(SENSE_HLO.replace(".1 =", ".7 =", 1), 4000, 2000,
                    **TPU_STATS),
                 ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)", 8000, 500,
                    **TPU_STATS),
                 ev("%copy.1 = s32[128]{0} copy(s32[128]{0} %a)", 20000, 500,
                    **TPU_STATS)],   # after the window
        XLA_Modules=[ev("jit__take(12)", 2500, 400),
                     ev("jit_run(3)", 3000, 6000)])
    data = types.SimpleNamespace(planes=[host, device,
                                         plane("/host:metadata")])
    return devtrace.Trace.from_profile(data)


def test_window_busy_and_idle(trace):
    assert trace.window == (1000, 11000)
    assert trace.devices == ["/device:TPU:0"]
    # ops cover [3000, 6000) and [8000, 8500): 3.5 us of a 10 us window
    assert trace.busy_s() == pytest.approx(3.5e-6)
    assert trace.window_s == pytest.approx(1e-5)
    gaps = dict(trace.idle_gaps())
    # idle [1000,3000): step [1500,2500), submit the rest; [6000,8000) and
    # [8500,10000) in the wait; [10000,11000) covered by no span
    assert gaps == pytest.approx({"bench.step": 1e-6, "bench.submit": 1e-6,
                                  "bench.wait": 3.5e-6, "host.other": 1e-6})
    assert trace.top_ops()[0] == ["mlc_sense", pytest.approx(4e-6)]
    assert [e.name for e in trace.module_events(r"^jit_+take\b")] == \
        ["jit__take(12)"]


def test_kernel_shapes_and_roofline(trace):
    calls = trace.kernel_calls("mlc_sense")
    assert len(calls) == 2
    seconds, operands, results = calls[0]
    assert operands == [("f32", (8,)), ("f32", (256, 131072))]
    assert results == [("u32", (256, 4096))]
    kernel = load_module(layers.BENCH / "kernels" / "mlc_sense.py")
    assert kernel.bytes_moved(operands, results) == SENSE_BYTES
    ctx = layers.Context(window=None, trace=trace,
                         peaks=layers.peaks("TPU v5 lite"))
    want = 100 * 2 * SENSE_BYTES / 819e9 / 4e-6
    assert ctx.roofline("mlc_sense") == pytest.approx(want)
    assert ctx.roofline("sense_reduce") is None     # no call: nothing read


def test_kernels_and_programs_found_by_metadata():
    """A kernel op named only ``custom-call.<n>`` is found by the jitted
    wrapper in its name stack; without an ``XLA Modules`` line, program
    time comes from the ops' ``hlo_module``."""
    hlo = SENSE_HLO.replace("%mlc_sense.1", "%custom-call.4") + \
        ', metadata={op_name="jit(run)/jit(mlc_sense)/pallas_call"}'
    device = plane("/device:TPU:0", XLA_Ops=[
        ev("custom-call.4", 2000, 1000, long_name=hlo),
        ev("fusion.1", 4000, 500, hlo_module="jit__take"),
        ev("fusion.9", 5000, 500, hlo_module="jit_run")])
    host = plane("/host:CPU", python=[ev("bench.window", 1000, 9000)])
    t = devtrace.Trace.from_profile(types.SimpleNamespace(
        planes=[host, device]))
    assert len(t.kernel_calls("mlc_sense")) == 1
    assert [e.name for e in t.module_events(r"^jit_+take\b")] == ["fusion.1"]


def test_hlo_shapes_needs_a_custom_call_with_operand_shapes():
    assert devtrace.hlo_shapes("%fusion.2 = f32[8]{0} fusion(%p)") is None
    assert devtrace.hlo_shapes(
        "%c.1 = u32[8,128]{1,0} custom-call(%a, %b)") is None
    printed = ("%c.1 = u32[8,128]{1,0} custom-call(f32[4]{0} %a, "
               "f32[8,4096]{1,0} %b), custom_call_target=\"tpu_custom_call\"")
    assert devtrace.hlo_shapes(printed) == (
        [("f32", (4,)), ("f32", (8, 4096))], [("u32", (8, 128))])


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError, match="no peaks"):
        layers.peaks("TPU v9 imaginary")


def test_trace_without_window_span_is_refused():
    data = types.SimpleNamespace(planes=[plane("/host:CPU", python=[
        ev("bench.step", 0, 10)])])
    with pytest.raises(ValueError, match="bench.window"):
        devtrace.Trace.from_profile(data)


def test_interval_arithmetic():
    a = devtrace.merge([(5, 7), (0, 2), (1, 3)])
    assert a == [(0, 3), (5, 7)]
    assert devtrace.intersect(a, [(2, 6)]) == [(2, 3), (5, 6)]
    assert devtrace.subtract([(0, 10)], a) == [(3, 5), (7, 10)]
    assert devtrace.length(a) == 5
