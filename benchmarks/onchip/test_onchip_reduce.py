"""The reduction from a profiler trace to metrics, without a chip.

A hand-made trace checks the interval arithmetic exactly; a trace
recorded on a TPU v5e (``fixtures/``) checks that ``load`` finds the
device's operations, the kernel's events and the benchmark's host spans,
and reduces them to the numbers the fixture's note records.
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import trace_reduce as tr  # noqa: E402
from trace_reduce import Op, Trace  # noqa: E402

FIXTURE = HERE / "fixtures" / "mnist_offline.xplane.pb"
NOTE = json.loads((HERE / "fixtures" / "mnist_offline.json").read_text())
MS = 1_000_000   # ns


@pytest.fixture
def made():
    """Window 0-100 ms. Device: fused_cwp 10-30 and 25-40 (overlapping),
    a transpose 60-70, an op half outside the window 95-120. Host:
    frontend_step 0-50, wait_arrival 50-100."""
    ops = [Op("fused_cwp.1", 10 * MS, 30 * MS, ""),
           Op("custom-call.2", 25 * MS, 40 * MS, "_fused_cwp_kernel"),
           Op("transpose.3", 60 * MS, 70 * MS, ""),
           Op("fusion.4", 95 * MS, 120 * MS, "")]
    spans = [("window", 0, 100 * MS), ("frontend_step", 0, 50 * MS),
             ("wait_arrival", 50 * MS, 100 * MS)]
    return Trace({"/device:TPU:0": ops}, spans)


def test_busy_is_the_union_of_operations_inside_the_window(made):
    assert tr.window_s(made) == pytest.approx(0.100)
    # 10-40 (union of the overlap) + 60-70 + 95-100
    assert tr.busy_s(made) == pytest.approx(0.045)


def test_kernel_time_sums_the_picked_events(made):
    pick = lambda op: "fused_cwp" in op.name or "fused_cwp" in op.detail
    assert tr.op_time_s(made, pick) == pytest.approx(0.035)


def test_top_ops_and_idle_gaps_by_host_span(made):
    assert tr.top_ops(made)[0] == ["fused_cwp.1", pytest.approx(0.020)]
    assert tr.short("%copy.4 = f32[8,15]{1,0} copy(f32[8,15] %x)") == \
        "%copy.4"
    # idle: 0-10, 40-50 in frontend_step; 50-60, 70-95 in wait_arrival
    assert dict(tr.idle_gaps(made)) == {
        "wait_arrival": pytest.approx(0.035),
        "frontend_step": pytest.approx(0.020)}


def test_devices_are_averaged(made):
    two = Trace({"/device:TPU:0": made.ops["/device:TPU:0"],
                 "/device:TPU:1": [Op("x", 0, 100 * MS)]}, made.spans)
    assert tr.busy_s(two) == pytest.approx((0.045 + 0.100) / 2)


@pytest.fixture(scope="module")
def recorded():
    return tr.load(str(FIXTURE))


def test_a_recorded_chip_trace_reduces_to_its_numbers(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    assert {name for name, _, _ in recorded.spans} >= {
        "window", "submit", "frontend_step"}
    assert tr.window_s(recorded) == pytest.approx(NOTE["window_s"])
    assert tr.busy_s(recorded) == pytest.approx(NOTE["busy_s"])
    assert 0 < NOTE["busy_s"] < NOTE["window_s"]
    pick = harness.load_module(HERE / "metrics" / "fused_cwp_roofline.py")
    kernel_s = tr.op_time_s(recorded, lambda op: pick.KERNEL in op.name
                            or pick.KERNEL in op.detail)
    assert kernel_s == pytest.approx(NOTE["fused_cwp_s"])
    assert 0 < kernel_s < NOTE["busy_s"]


def test_recorded_roofline_and_idle_share(recorded):
    """The per-layer readers over the recorded trace and the engine
    counters of the recorded window."""
    cell = harness.resolve("mnist_cnn.offline")
    none = np.zeros(0)
    run = harness.Run(
        cell, seconds=NOTE["window_s"], setup_s=0.0, due=none,
        dispatch=none, finish=none, done_in_window=NOTE["images"],
        engine=NOTE["engine"],
        stages=cell.family.stages(cell.config),
        flops_per_image=cell.family.flops_per_image(cell.config),
        peak=json.loads((HERE / "peaks.json").read_text())
        ["devices"]["TPU v5 lite"], trace=recorded)
    read = {m["name"]: mod.read(run) for m, mod in cell.metrics[1]}
    assert read["fused_cwp_roofline"] == pytest.approx(
        NOTE["fused_cwp_roofline"])
    assert 0 < read["fused_cwp_roofline"] < 100
    assert read["device_idle_share"] == pytest.approx(
        100 * (1 - NOTE["busy_s"] / NOTE["window_s"]))
