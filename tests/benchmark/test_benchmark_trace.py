"""The reduction from a profiler trace to the per-layer numbers: on
hand-made timelines, where every answer is known, and on a small trace
recorded on a TPU v5e (``data/v5e_small.xplane.pb``: a jitted bf16 1024^3
matmul + tanh + sum, called in a loop with the benchmark's host spans
around it, traced for 0.15 s)."""

import os

import pytest

import bench_paths
from harness import trace

RECORDED = os.path.join(bench_paths.DATA, "v5e_small.xplane.pb")


def _timeline(index, ops, modules=()):
    return trace.DeviceTimeline(index, list(ops), list(modules))


def test_union_gaps_and_clip():
    busy = trace.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (4.0, 4.0)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert trace.total(busy) == 3.0
    assert trace.gaps(busy, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert trace.gaps(busy, 1.0, 3.5) == [(2.0, 3.0)]
    assert trace.clip(busy, 1.5, 3.5) == [(1.5, 2.0), (3.0, 3.5)]


def test_busy_window_steps_and_collectives_on_two_chips():
    ar = ("%all-reduce.7 = f32[2048,1000]{1,0:T(8,128)} all-reduce("
          "f32[2048,1000]{1,0} %x), replica_groups={{0,1,2,3}}")
    ops0 = [("fusion.1", 0.0, 1.0), (ar, 1.0, 1.5),
            ("fusion.1", 2.0, 3.0), (ar, 3.0, 3.5)]
    ops1 = [("fusion.1", 0.0, 1.0), (ar, 1.0, 2.0),
            ("fusion.1", 2.0, 3.0), (ar, 3.0, 4.0)]
    mods = [("jit_step", 0.0, 1.5), ("jit_step", 2.0, 3.5),
            ("jit_norms", 3.6, 3.7)]
    tr = trace.Trace([_timeline(0, ops0, mods), _timeline(1, ops1, mods)], [])
    busy_s, window_s = trace.busy_seconds(tr)
    assert window_s == 4.0                       # first start to last end
    assert busy_s == pytest.approx((3.0 + 4.0) / 2)
    assert trace.steps(tr) == 2                  # the program with most time
    assert trace.collective_seconds(tr) == pytest.approx(2.0)   # worst chip
    top = trace.top_ops(tr, 10)
    assert top[0] == ["fusion.1", pytest.approx(2.0)]
    assert top[1] == ["%all-reduce.7 f32[2048,1000]", pytest.approx(1.5)]


def test_short_name_keeps_the_instruction_and_its_largest_result():
    fusion = ("%multiply_reduce_fusion.1 = (bf16[256]{0:T(256)(128)(2,1)S(1)},"
              " bf16[128,56,56,256]{3,0,2,1:T(8,128)(2,1)}) fusion(bf16[128,56,"
              "56,256]{3,0,2,1} %get-tuple-element.1101), kind=kOutput")
    assert trace.short_name(fusion) == \
        "%multiply_reduce_fusion.1 bf16[128,56,56,256]"
    assert trace.short_name("jit_step") == "jit_step"


def test_idle_is_split_by_the_host_span_that_covered_it():
    ops = [("fusion", 0.0, 1.0), ("fusion", 3.0, 4.0), ("fusion", 6.0, 7.0)]
    spans = [("bench.fit_call", 0.0, 7.0),       # outer
             ("bench.input_wait", 1.0, 2.5),     # inner: wins where it lies
             ("bench.input_wait", 4.0, 4.5)]
    tr = trace.Trace([_timeline(0, ops)], spans)
    got = dict(map(tuple, trace.idle_by_host_span(tr)))
    assert got["bench.input_wait"] == pytest.approx(1.5 + 0.5)
    assert got["bench.fit_call"] == pytest.approx(0.5 + 1.5)
    assert sum(got.values()) == pytest.approx(4.0)
    # with no host span at all, idle time is still accounted for
    bare = trace.Trace([_timeline(0, ops)], [])
    assert trace.idle_by_host_span(bare) == [["host_unattributed",
                                              pytest.approx(4.0)]]


def test_a_trace_with_no_device_operation_reads_as_nothing():
    tr = trace.Trace([], [("bench.fit_call", 0.0, 1.0)])
    assert trace.busy_seconds(tr) == (0.0, 0.0)
    assert trace.steps(tr) == 0 and trace.top_ops(tr) == []
    assert trace.idle_by_host_span(tr) == []


@pytest.fixture(scope="module")
def recorded():
    return trace.load(RECORDED)


def test_recorded_v5e_trace_planes_and_spans(recorded):
    assert [d.index for d in recorded.devices] == [0]
    dev = recorded.devices[0]
    assert len(dev.ops) > 10 and len(dev.modules) > 3
    names = {name for name, _, _ in recorded.host_spans}
    assert names == {"bench.input_wait", "bench.fit_call"}
    # host spans and device operations sit on one clock, to a couple of
    # milliseconds (in this trace the device's events read about 1.5 ms
    # early): every program execution starts near a fit_call's start
    calls = [s for n, s, e in recorded.host_spans if n == "bench.fit_call"]
    near = sum(any(abs(ms - s) < 5e-3 for s in calls)
               for _, ms, _ in dev.modules)
    assert near >= len(dev.modules) - 1


def test_recorded_v5e_trace_reduces_to_sane_numbers(recorded):
    busy_s, window_s = trace.busy_seconds(recorded)
    assert 0.0 < busy_s < window_s < 0.5
    steps = trace.steps(recorded)
    assert steps == max(
        sum(1 for n, _, _ in recorded.devices[0].modules if n == name)
        for name in {n for n, _, _ in recorded.devices[0].modules})
    # the loop sleeps 4 ms a turn: the chip is mostly idle, and most of
    # that idle time lies under the input_wait span
    assert busy_s / window_s < 0.5
    idle = dict(map(tuple, trace.idle_by_host_span(recorded)))
    assert idle["bench.input_wait"] == max(idle.values())
    assert sum(idle.values()) == pytest.approx(window_s - busy_s, rel=1e-6)
    # a 1024^3 bf16 matmul is 2.1 GFLOP: its time has to be at or above
    # what the published peak allows, or the FLOP count or the clock is off
    per_step = busy_s / steps
    assert per_step >= 2 * 1024 ** 3 / 197e12
    assert trace.collective_seconds(recorded) == 0.0
    top = trace.top_ops(recorded, 10)
    assert 1 <= len(top) <= 10 and top[0][1] > 0
