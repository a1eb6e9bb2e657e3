"""chipbench/trace_reduce.py on planes made by hand, and on a small
trace recorded on the chip (data/step.xplane.pb; see record_fixture.py
and cut_xplane.py for how it was made)."""

import os

import pytest

from chipbench import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "step.xplane.pb")


def test_op_and_module_groups():
    assert tr.op_group("fusion.123") == "fusion"
    assert tr.op_group("%dynamic-update-slice.5 = s32[3]{0} ...") == \
        "dynamic-update-slice"
    assert tr.op_group("sort") == "sort"
    assert tr.op_group("copy.1.2") == "copy"
    assert tr.module_group("jit__step(123456789)") == "jit__step"


def test_union_and_gaps():
    busy = tr.union([[10_000, 20_000], [15_000, 30_000], [50_000, 60_000],
                     [60_000, 61_000]])
    assert busy == [[10_000, 30_000], [50_000, 61_000]]
    assert tr.gaps(busy, 0, 100_000) == [(0, 10_000), (30_000, 50_000),
                                         (61_000, 100_000)]
    # Gaps under 1 us are the device's own pipelining, not idleness.
    assert tr.gaps([[0, 1000], [1500, 3000]], 0, 3000) == []


def hand_planes():
    us = 1000.0
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            (100 * us, 50 * us, "jit__step(11)"),
            (300 * us, 50 * us, "jit__step(22)"),
            (600 * us, 10 * us, "jit_convert_element_type(3)")]},
        {"name": "XLA Ops", "events": [
            (100 * us, 30 * us, "fusion.1"), (130 * us, 20 * us, "sort.7"),
            (300 * us, 30 * us, "fusion.2"), (330 * us, 20 * us, "sort.7"),
            (600 * us, 10 * us, "copy.3")]}]}
    host = {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [
            (0.0, 1000 * us, "$selectors.py:402 select"),
            (160 * us, 120 * us, "ratelimiter/tpu_sketch/launch"),
            (200 * us, 20 * us, "PjitFunction(_step)"),
            (360 * us, 200 * us, "$native_server.py:300 _resolve")]}]}
    return [device, host]


def test_reduce_hand_planes():
    out = tr.reduce_planes(hand_planes())
    assert out["n_devices"] == 1
    # The window is the device's own span, 100 us .. 610 us; the host
    # plane's longer span (the profiler starting and stopping) is not.
    assert out["window_s"] == pytest.approx(510e-6)
    assert out["busy_s"] == pytest.approx(110e-6)
    assert out["idle_pct"] == pytest.approx(100 * 400 / 510)
    assert out["step"] == {"module": "jit__step", "executions": 2,
                           "seconds": pytest.approx(100e-6)}
    assert out["device_ops"][:3] == [["fusion", pytest.approx(60e-6)],
                                     ["sort", pytest.approx(40e-6)],
                                     ["copy", pytest.approx(10e-6)]]
    gaps = dict(map(tuple, out["idle_gaps"]))
    # 150-300 us: its middle (225 us) lies under launch, PjitFunction over.
    assert gaps["ratelimiter/tpu_sketch/launch"] == pytest.approx(150e-6)
    # 350-600 us: middle 475 us, inside the traced Python call only.
    assert gaps["$native_server.py:300 _resolve"] == pytest.approx(250e-6)
    assert len(gaps) == 2 and out["devices"][0]["gaps"] == 2


def test_innermost_traceme_wins_over_python_frames():
    planes = hand_planes()
    planes[1]["lines"][0]["events"].append(
        (210_000.0, 30_000.0, "$jit.py:1 inner"))
    out = tr.reduce_planes(planes)
    assert "$jit.py:1 inner" not in dict(map(tuple, out["idle_gaps"]))


def test_no_host_event_is_named_so():
    planes = hand_planes()[:1]
    out = tr.reduce_planes(planes)
    assert [g[0] for g in out["idle_gaps"]] == ["host-outside-jax"]


def test_four_chips_average():
    planes = hand_planes()
    second = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Modules", "events": [(0.0, 500_000.0, "jit__step(9)")]},
        {"name": "XLA Ops", "events": [(0.0, 500_000.0, "fusion.4")]}]}
    out = tr.reduce_planes(planes + [second])
    assert out["n_devices"] == 2
    # The window is the union span of the device planes: 0 .. 610 us.
    assert out["idle_pct"] == pytest.approx(
        100 * ((1 - 110 / 610) + (1 - 500 / 610)) / 2)
    assert out["step"]["executions"] == 3


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_recorded_trace_from_the_chip():
    assert os.path.getsize(FIXTURE) < 1 << 20
    out = tr.reduce_planes(tr.read_planes(FIXTURE))
    # 25 ms of wide-hashed-sat on one v5e: six dispatches of the step.
    assert out["n_devices"] == 1
    assert out["window_s"] == pytest.approx(0.025802446)
    assert out["busy_s"] == pytest.approx(0.006327864)
    assert out["idle_pct"] == pytest.approx(75.4757, abs=1e-3)
    assert out["step"]["module"] == "jit__unknown"
    assert out["step"]["executions"] == 6
    assert [m[:2] for m in out["modules"]] == [
        ["jit__unknown", 6], ["jit_finish_window", 5],
        ["jit_convert_element_type", 16]]
    assert out["device_ops"][0] == ["fusion", pytest.approx(0.004511608)]
    assert "sort" in dict(map(tuple, out["device_ops"]))
    assert out["idle_gaps"][0][0] == "DeferredTpuAllocator::Allocate"
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) == 10
    idle = sum(sec for _, sec in tr.reduce_planes(
        tr.read_planes(FIXTURE))["idle_gaps"])
    assert idle <= out["window_s"] - out["busy_s"] + 1e-9
    busy = sum(sec for _, sec in out["device_ops"])
    assert busy >= out["busy_s"] * 0.99         # the ten largest groups
