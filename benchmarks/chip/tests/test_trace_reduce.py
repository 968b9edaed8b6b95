"""The trace reduction, on a trace recorded on a TPU v5e chip and on
hand-made intervals.

``data/pass_trace.xplane.pb.gz`` is the profiler's XSpace of a short
``--trace 1`` run of ``pass.whisper_tiny.k16`` (one chip, gzipped).
"""
import gzip

import pytest
from jax.profiler import ProfileData

import trace_reduce as tr
from common import HERE, SPAN_WINDOW

TRACE = HERE / "tests" / "data" / "pass_trace.xplane.pb.gz"


@pytest.fixture(scope="module")
def pd():
    return ProfileData.from_serialized_xspace(
        gzip.decompress(TRACE.read_bytes()))


@pytest.fixture(scope="module")
def red(pd):
    return tr.reduce_trace(pd)


def _line(pd, plane_name, line_name):
    plane = next(p for p in pd.planes if p.name == plane_name)
    line = next(ln for ln in plane.lines if ln.name == line_name)
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def test_window_is_the_bench_span(pd, red):
    spans = [s for s in tr.read_planes(pd)[1] if s[0] == SPAN_WINDOW]
    assert len(spans) == 1
    assert red.window_s == pytest.approx((spans[0][2] - spans[0][1]) / 1e9)
    assert red.chips == 1


def test_busy_agrees_with_the_module_line(pd, red):
    """Ops run inside the compiled programs' executions: their union
    lies within that of the 'XLA Modules' line, and covers nearly all of
    it (the rest is the launch of each program on the chip)."""
    _, spans = tr.read_planes(pd)
    _, lo, hi = next(s for s in spans if s[0] == SPAN_WINDOW)
    modules = tr.union(tr.clip(_line(pd, "/device:TPU:0", "XLA Modules"),
                               lo, hi))
    mod_s = sum(e - s for s, e in modules) / 1e9
    assert 0 < red.busy_s <= mod_s
    assert red.busy_s >= 0.98 * mod_s


def test_kernel_leads_the_device_ops(red):
    name, secs = red.top_ops[0]
    assert name.endswith("custom-call")  # the fused Pallas kernel
    assert secs > 0.8 * red.busy_s
    assert sum(red.op_seconds.values()) == pytest.approx(red.busy_s,
                                                         rel=1e-6)


def test_idle_is_named_by_bench_spans(red):
    idle = dict(red.idle_gaps)
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s,
                                               abs=1e-6)
    assert max(idle, key=idle.get) == "bench.sync"


def test_op_names():
    assert tr.op_name("%sub.1 = f32[8]{0:T(1024)} subtract(f32[8] %a, "
                      "f32[8] %b)") == "sub.1 subtract"
    assert tr.op_name("%fusion.2 = (f32[4], s32[]) fusion(f32[4] %p), "
                      "kind=kLoop") == "fusion.2 fusion"


def test_self_times_leave_out_nested_ops():
    ops = [("loop", 0, 100), ("a", 10, 30), ("b", 40, 90), ("c", 50, 60),
           ("d", 120, 130)]
    assert tr.self_times(ops) == {"loop": 30, "a": 20, "b": 40, "c": 10,
                                  "d": 10}


def test_union_and_gaps():
    busy = tr.union([(5, 10), (0, 3), (8, 12), (20, 25)])
    assert busy == [(0, 3), (5, 12), (20, 25)]
    assert tr.gaps(busy, 0, 30) == [(3, 5), (12, 20), (25, 30)]


def test_gap_goes_to_the_innermost_span():
    index = tr._SpanIndex([("bench.call", 0, 100), ("bench.gather", 20, 40),
                           ("bench.sync", 60, 70)])
    assert index.attribute(10, 80) == {"bench.call": 40, "bench.gather": 20,
                                       "bench.sync": 10}
    assert index.attribute(150, 160) == {tr.NO_SPAN: 10}
