"""``perfbench/xplane.py``: the reductions on a trace small enough to work
out by hand, and the reader on a small trace recorded on a TPU v5e."""

from pathlib import Path

import numpy as np
import pytest

from perfbench import xplane
from perfbench.xplane import Event, Trace

RECORDED = Path(__file__).parent / "data" / "small_v5e.xplane.pb"


def by_hand() -> Trace:
    """Two devices, times in ns, window 0..150.

    d0: a ``while`` 0..100 covering a 10..30, b 40..70 and an all-gather
    75..85; then c 120..130. Programs: m 0..100, m2 120..130.
    d1: an all-gather 0..50 overlapped by x 20..60 (a neighbour: it outlasts
    the all-gather), one program m 0..60.
    Host spans: the window, step 0..115, tail 115..150."""
    d0 = [Event("while", 0, 100), Event("a", 10, 20), Event("b", 40, 30),
          Event("all-gather.1", 75, 10), Event("c", 120, 10)]
    d1 = [Event("all-gather.1", 0, 50), Event("x", 20, 40)]
    return Trace(
        ops={"d0": d0, "d1": d1},
        modules={"d0": [Event("jit_m(17)", 0, 100), Event("jit_m2(3)", 120, 10)],
                 "d1": [Event("jit_m(17)", 0, 60)]},
        # d1's all-gather was in flight from 0 to 55
        in_flight={"d1": [Event("all-gather.1", 0, 55)]},
        host=[Event("window", 0, 150), Event("step", 0, 115),
              Event("tail", 115, 35)],
        window=(0.0, 150.0), lines={}, categories={},
        results={"while": "(s32[], f32[8])"})


def test_busy_is_the_union_of_intervals_averaged_over_devices():
    t = by_hand()
    # d0: 0..100 and 120..130 = 110; d1: 0..60 = 60; mean 85 ns
    assert xplane.busy_seconds(t, 0, 150) == pytest.approx(85e-9)
    # clipped to 50..125: d0 50 + 5, d1 10
    assert xplane.busy_seconds(t, 50, 125) == pytest.approx((55 + 10) / 2 * 1e-9)


def test_self_time_takes_nested_operations_out_of_their_parent():
    got = {e.name: (s, leaf) for e, s, leaf in xplane.self_times(by_hand().ops["d0"])}
    assert got == {"while": (40, False), "a": (20, True), "b": (30, True),
                   "all-gather.1": (10, True), "c": (10, True)}
    # an operation that outlasts the one before it is no child of it
    got = {e.name: (s, leaf) for e, s, leaf in xplane.self_times(by_hand().ops["d1"])}
    assert got == {"all-gather.1": (50, True), "x": (40, True)}


def test_per_program_and_per_operation_sums():
    t = by_hand()
    programs = xplane.program_seconds(t, 0, 150)
    # device-averaged: m (100 + 60) / 2, m2 10 / 2; calls rounded from 1, 0.5
    assert programs["jit_m"] == (pytest.approx(80e-9), 1)
    assert programs["jit_m2"][0] == pytest.approx(5e-9)
    ops = xplane.op_seconds(t, 0, 150)
    assert ops["while"] == pytest.approx(20e-9) and ops["a"] == pytest.approx(10e-9)
    assert ops["all-gather.1"] == pytest.approx((10 + 50) / 2 * 1e-9)
    inside_m2 = xplane.op_seconds(t, 0, 150, inside=lambda p: p == "jit_m2")
    assert inside_m2 == {"c": pytest.approx(5e-9)}


def test_collective_time_and_the_part_of_it_that_is_exposed():
    total, exposed = xplane.collective_seconds(by_hand(), 0, 150)
    # d0: 10 ns, nothing beside it (the while is no leaf) -> 10 exposed;
    # d1: executing 0..50 and in flight until 55, x runs beside it 20..60
    # -> 20 exposed
    assert total == pytest.approx((10 + 55) / 2 * 1e-9)
    assert exposed == pytest.approx((10 + 20) / 2 * 1e-9)


def test_idle_gaps_go_to_the_innermost_host_span_over_their_middle():
    gaps = xplane.idle_gaps(by_hand(), 0, 150)
    # first device d0 idles 100..120 (middle 110: step) and 130..150 (tail)
    assert gaps == {"step": pytest.approx(20e-9), "tail": pytest.approx(20e-9)}
    both = xplane.breakdown(by_hand(), 0, 150)
    assert both["device_ops"][0] == ["all-gather.1", pytest.approx(30e-9)]
    assert ["while (s32[], f32[8])", pytest.approx(20e-9)] in both["device_ops"]
    assert len(both["idle_gaps"]) == 2


def test_interval_arithmetic():
    assert xplane.merged([(5, 7), (0, 2), (1, 3), (9, 12)], 0, 10) == \
        [(0, 3), (5, 7), (9, 10)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert xplane.program_name("jit_generation(12345)") == "jit_generation"
    # names seen on four v5e chips in PR 22
    assert all(xplane.is_collective(n) for n in (
        "all-gather.160", "async-collective-done.5", "async-collective-start.7",
        "reduce-scatter.3", "all-reduce-start.1"))
    assert not any(xplane.is_collective(n) for n in (
        "fusion.254", "shard_map.453", "copy-start.53", "slice-start.1"))


def test_an_operation_is_named_by_its_hlo_name_and_opcode():
    assert xplane.operation(
        "%while = (s32[]{:T(128)}, bf16[8,8]{1,0:T(8,128)(2,1)S(1)}) "
        "while((s32[]{:T(128)}, bf16[8,8]{1,0}) %tuple.13), condition=%c, "
        "body=%b") == ("while", "while", "(s32[], bf16[8,8])")
    assert xplane.operation(
        "%all-gather-start.2 = (f32[4]{0}, f32[16]{0}) all-gather-start("
        "f32[4]{0} %p), dimensions={0}") == (
            "all-gather-start.2", "all-gather-start", "(f32[4], f32[16])")
    assert xplane.operation("ThunkExecutor::Execute") == (
        "ThunkExecutor::Execute", "", "")


def recorded() -> Trace:
    return xplane.load(RECORDED, window_span="pb/window")


def test_recorded_v5e_trace_is_read_line_by_line():
    """Three calls of one small jitted program (a 3-step scan of a 1024 x
    1024 bf16 matmul + tanh, then a sum), recorded on a TPU v5e in PR 22."""
    t = recorded()
    assert list(t.ops) == list(t.modules) == ["/device:TPU:0"]
    assert t.lines["/device:TPU:0"] == {
        "XLA Modules": 3, "XLA Ops": 36, "Async XLA Ops": 6, "TC Overlay": 0}
    assert [e.name for e in t.host] == ["window", "step", "step", "step"]
    window = t.host[0]
    assert t.window == (window.start - xplane.CLOCK_SKEW_NS, window.end)
    # the device's clock is ahead: its first program "starts" before the
    # host span that dispatched it, and the widened window still holds it
    assert t.modules["/device:TPU:0"][0].start < window.start
    assert xplane.program_seconds(t, *t.window)["jit_small_step"][1] == 3
    # read off the file by hand: each call runs 12 operations, among them
    # one while over 3 x (copy, fusion), and two copies in flight beside
    assert len(t.ops["/device:TPU:0"]) == 36
    names = {e.name for e in t.ops["/device:TPU:0"]}
    assert names == {"copy-start", "copy-start.1", "copy-done", "copy-done.1",
                     "while", "copy.11", "convolution_tanh_fusion.2", "reduce"}
    assert t.categories["convolution_tanh_fusion.2"] == "fusion"
    assert t.categories["while"] == "while"
    assert t.results["convolution_tanh_fusion.2"] == "bf16[1024,1024]"
    first = t.modules["/device:TPU:0"][0]
    assert xplane.program_name(first.name) == "jit_small_step"
    assert (first.start, first.dur) == (43639063.0, 44131.0)


def test_recorded_v5e_trace_reductions_against_a_count_made_another_way():
    """Busy time, per-program and per-operation sums over the device's own
    extent, against a nanosecond-by-nanosecond timeline (the device's clock
    runs 1-2 ms before the host's here, so the host's window is not used)."""
    t = recorded()
    ops, modules = t.ops["/device:TPU:0"], t.modules["/device:TPU:0"]
    lo, hi = modules[0].start, modules[-1].end
    timeline = np.zeros(int(hi - lo) + 1, bool)
    for e in ops:
        timeline[int(e.start - lo):int(e.end - lo)] = True
    busy = xplane.busy_seconds(t, lo, hi)
    assert busy == pytest.approx(timeline.sum() / 1e9, rel=1e-3)
    # the three programs run back to back with host time between them:
    # the device is busy for their 3 x ~44 us out of ~6.5 ms
    programs = xplane.program_seconds(t, lo, hi)
    assert programs["jit_small_step"][1] == 3
    assert programs["jit_small_step"][0] == pytest.approx(
        sum(m.dur for m in modules) / 1e9)
    assert busy == pytest.approx(programs["jit_small_step"][0], rel=0.01)
    assert 130e-6 < busy < 135e-6 and 6e-3 < (hi - lo) / 1e9 < 8e-3
    # self time: the while's 3 x (copy + fusion) are taken out of it, and
    # every operation's self time adds up to the busy time
    per_op = xplane.op_seconds(t, lo, hi)
    assert sum(per_op.values()) == pytest.approx(busy, rel=1e-3)
    assert per_op["while"] < 1e-6 < per_op["convolution_tanh_fusion.2"]
    # 9 fusions of 2 * 1024^3 FLOPs in that time: a matmul near the bf16 peak
    assert 0.9 * 197e12 < 2 * 1024 ** 3 * 9 / per_op["convolution_tanh_fusion.2"] < 197e12
    assert xplane.collective_seconds(t, lo, hi) == (0.0, 0.0)
