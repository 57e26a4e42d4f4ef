"""The reduction of the program's spans (``program_trace``), without a
chip.

A hand-made trace checks self time, executable runs and the idle time
put down to the innermost program span exactly; the trace recorded on a
TPU v5e before the program had spans (``fixtures/mnist_offline.*``)
checks that without program spans the breakdown is the benchmark's own;
one recorded with them (``fixtures/mnist_offline_spans.*``,
``tools/record_fixture.py``) checks that ``load`` finds the spans and
the bucket modules, that the spans cover the step the benchmark times,
and that the reduction gives the numbers its note records.
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import program_trace as pt  # noqa: E402
import trace_reduce as tr  # noqa: E402
from trace_reduce import Op  # noqa: E402

MS = 1_000_000   # ns


@pytest.fixture
def nested():
    """Window 0-100 ms. Device: 10-20 (in jit_vision_b8) and 60-70.
    Benchmark spans: frontend_step 0-50, wait_arrival 50-100. Program
    spans: frontend.step 0-50 holding vision.step 5-45, whose children
    are place 5-10, launch 10-12, fetch 12-40, deliver 40-45; a second
    frontend.step 95-120, half outside the window."""
    ops = [Op("%fused_cwp.s1.1", 10 * MS, 20 * MS),
           Op("%copy.2", 60 * MS, 70 * MS)]
    spans = [("window", 0, 100 * MS), ("frontend_step", 0, 50 * MS),
             ("wait_arrival", 50 * MS, 100 * MS)]
    program = [("frontend.step", 0, 50 * MS),
               ("vision.step", 5 * MS, 45 * MS),
               ("vision.place", 5 * MS, 10 * MS),
               ("vision.launch", 10 * MS, 12 * MS),
               ("vision.fetch", 12 * MS, 40 * MS),
               ("vision.deliver", 40 * MS, 45 * MS),
               ("frontend.step", 95 * MS, 120 * MS)]
    modules = [Op("jit_vision_b8(1)", 9 * MS, 21 * MS),
               Op("jit_other(2)", 59 * MS, 71 * MS)]
    return pt.ProgramTrace({"/device:TPU:0": ops}, spans, program,
                           {"/device:TPU:0": modules})


def test_self_time_leaves_out_the_children(nested):
    assert pt.span_times(nested, "frontend.step") == (
        2, pytest.approx(0.055), pytest.approx(0.015))
    assert pt.span_times(nested, "vision.step") == (
        1, pytest.approx(0.040), pytest.approx(0.0))
    assert pt.span_times(nested, "vision.fetch") == (
        1, pytest.approx(0.028), pytest.approx(0.028))
    assert pt.span_times(nested, "vision.other") == (0, 0.0, 0.0)


def test_idle_goes_to_the_innermost_program_span(nested):
    # idle 0-10: frontend.step 0-5, place 5-10; 20-60: fetch 20-40,
    # deliver 40-45, frontend.step 45-50, no program span 50-60; 70-100:
    # no program span 70-95, frontend.step 95-100
    assert dict(pt.idle_gaps(nested)) == {
        "wait_arrival": pytest.approx(0.035),
        "vision.fetch": pytest.approx(0.020),
        "frontend.step": pytest.approx(0.015),
        "vision.place": pytest.approx(0.005),
        "vision.deliver": pytest.approx(0.005)}


def test_module_times_pick_the_bucket_programs(nested):
    assert pt.module_times(nested, lambda op: op.name.startswith(
        "jit_vision_b")) == [pytest.approx(0.012)]


def test_summary_of_the_serving_spans(nested):
    got = pt.summary(nested)
    assert got["spans"]["vision.launch"] == [
        1, pytest.approx(0.002), pytest.approx(0.002)]
    assert got["vision_modules"] == [1, pytest.approx(0.012)]
    assert got["idle_gaps"] == pt.idle_gaps(nested)
    assert got["busy_s"] == pytest.approx(0.020)


def test_without_a_window_span_the_program_spans_bound_it(nested):
    served = pt.ProgramTrace(nested.ops, [], nested.program, nested.modules)
    assert served.window() == (0, 120 * MS)
    assert pt.span_times(served, "frontend.step") == (
        2, pytest.approx(0.075), pytest.approx(0.035))
    with pytest.raises(ValueError):
        pt.ProgramTrace(nested.ops, []).window()


@pytest.fixture(scope="module")
def unspanned():
    return pt.load(str(HERE / "fixtures" / "mnist_offline.xplane.pb"))


def test_a_trace_without_program_spans_keeps_the_benchmark_breakdown(
        unspanned):
    note = json.loads((HERE / "fixtures" / "mnist_offline.json")
                      .read_text())
    assert unspanned.program == []
    assert pt.idle_gaps(unspanned) == tr.idle_gaps(unspanned)
    assert dict(pt.idle_gaps(unspanned)) == pytest.approx(
        dict(note["idle_gaps"]))
    # the parent's lambda executables: one run per engine step, none of
    # them a named bucket program
    lo, hi = unspanned.window()
    runs = [o for o in unspanned.modules["/device:TPU:0"]
            if lo <= o.start and o.end <= hi]
    assert len(runs) == note["engine"]["steps"]
    assert pt.summary(unspanned)["vision_modules"] == [0, None]


@pytest.fixture(scope="module")
def spanned():
    fixture = HERE / "fixtures" / "mnist_offline_spans"
    return (pt.load(str(fixture) + ".xplane.pb"),
            json.loads(Path(str(fixture) + ".json").read_text()))


def test_a_traced_window_with_program_spans_reduces_to_its_numbers(spanned):
    trace, note = spanned
    got, want = pt.summary(trace), note["program"]
    assert got["window_s"] == pytest.approx(want["window_s"])
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert set(got["spans"]) == set(want["spans"])
    for name, times in got["spans"].items():
        assert times == pytest.approx(want["spans"][name]), name
    assert got["vision_modules"] == pytest.approx(want["vision_modules"])
    assert dict(got["idle_gaps"]) == pytest.approx(dict(want["idle_gaps"]))
    steps = note["engine"]["steps"]
    assert [c for c, _, _ in got["spans"].values()] == [steps] * 6
    assert got["vision_modules"][0] == steps


def test_the_spans_cover_the_step_the_benchmark_times(spanned):
    _, note = spanned
    spans, steps = note["program"]["spans"], note["engine"]["steps"]
    children = sum(spans[name][1] for name in (
        "vision.place", "vision.launch", "vision.fetch", "vision.deliver"))
    engine_step = note["engine"]["wall_s"] / steps
    assert children / steps == pytest.approx(engine_step, rel=0.1)
    sched = spans["frontend.step"][2] / spans["vision.step"][0]
    vision_step = spans["vision.step"][1] / spans["vision.step"][0]
    count, total = note["frontend_step"]
    assert sched + vision_step == pytest.approx(total / count, rel=0.1)


def test_idle_time_goes_to_the_parts_of_the_step(spanned):
    trace, note = spanned
    idle = dict(pt.idle_gaps(trace))
    assert {"vision.fetch", "vision.launch", "vision.place",
            "frontend.step"} <= set(idle)
    assert max(idle, key=idle.get) == "vision.fetch"
    # the benchmark's own breakdown puts nearly all of it in one span
    assert dict(tr.idle_gaps(trace)) == pytest.approx(
        dict(note["idle_gaps"]))
    assert max(dict(note["idle_gaps"]).items(),
               key=lambda kv: kv[1])[0] == "frontend_step"


def test_the_kernels_carry_their_stage_names(spanned):
    trace, note = spanned
    assert set(note["kernel_events"]) == {
        "%fused_cwp.s1.1", "%fused_cwp.s2.1", "%copy.5"}
    names = {tr.short(o.name) for o in trace.ops["/device:TPU:0"]}
    assert not any("_fused_cwp_jit" in name for name in names)
    cell = harness.resolve(note["workload"])
    none = np.zeros(0)
    run = harness.Run(
        cell, seconds=note["window_s"], setup_s=0.0, due=none,
        dispatch=none, finish=none, done_in_window=note["images"],
        engine=note["engine"], stages=cell.family.stages(cell.config),
        flops_per_image=cell.family.flops_per_image(cell.config),
        peak=json.loads((HERE / "peaks.json").read_text())
        ["devices"][note["device"]], trace=trace)
    read = {m["name"]: mod.read(run) for m, mod in cell.metrics[1]}
    for name in ("engine_step_ms", "fused_cwp_roofline",
                 "device_idle_share"):
        assert read[name] == pytest.approx(note["metrics"][name]), name


def test_record_fixture_reads_the_hold_over_the_window():
    from repro.serve.stats import ServeStats
    tool = harness.load_module(HERE / "tools" / "record_fixture.py")
    stats = ServeStats(hold_s=1.0, holds=2)     # set-up's, not counted
    spans = tool.Spans(stats)
    with spans("window"):
        with spans("frontend_step"):
            stats.holds += 3
            stats.hold_s += 0.5
    stats.holds += 1                            # the drain's, not counted
    stats.hold_s += 20.0
    assert spans.hold == [3, pytest.approx(0.5)]
