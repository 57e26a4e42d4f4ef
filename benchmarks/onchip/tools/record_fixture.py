"""Record a short traced window of one cell as a test fixture: the
profiler trace (``<out>.xplane.pb``) and the numbers the reduction reads
from it (``<out>.json``), which ``test_onchip_program_trace.py`` holds
it to.

    python3 benchmarks/onchip/tools/record_fixture.py \\
        --workload mnist_cnn.offline --seed 7 --seconds 0.1 \\
        --out chiprun_out/mnist_offline_spans

from the repository root, on a machine with the cell's chips. Set-up,
the window and the trace are the harness's own (``harness.setup`` and
``harness.measure`` with the profiler on); the program's spans are read
with ``program_trace``. Nothing is checked against the reference.
"""
import argparse
import contextlib
import json
import shutil
import sys
import tempfile
from collections import Counter
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import harness  # noqa: E402
import program_trace as pt  # noqa: E402
import trace_reduce as tr  # noqa: E402


class Spans:
    """The benchmark's spans, as ``harness.measure`` opens them, that
    also read the top-up hold counters (``holds``, ``hold_s``) as the
    ``window`` span opens and closes."""

    def __init__(self, stats):
        from jax.profiler import TraceAnnotation
        self.annotate = TraceAnnotation
        self.stats = stats
        self.hold = None            # [holds, hold_s] over the window

    @contextlib.contextmanager
    def __call__(self, name: str):
        with self.annotate(name):
            if name != "window":
                yield
                return
            before = self.stats.holds, self.stats.hold_s
            try:
                yield
            finally:
                self.hold = [self.stats.holds - before[0],
                             self.stats.hold_s - before[1]]


def note(run: harness.Run, seed: int, device: str, hold: list) -> dict:
    """The numbers a fixture's test compares: the window, the device's
    busy time, the kernel's events, the engine counters, every per-layer
    metric of the cell, the benchmark's ``frontend_step`` spans (count
    and total seconds), its breakdown, the program's spans as
    ``program_trace.summary`` reduces them, and the top-up hold over the
    window."""
    t = run.trace
    lo, hi = t.window()
    roofline = harness.load_module(HERE.parent / "metrics"
                                   / "fused_cwp_roofline.py")

    def pick(op):
        return roofline.KERNEL in op.name or roofline.KERNEL in op.detail

    step_spans = [e - s for name, s, e in t.spans
                  if name == "frontend_step" and lo <= s and e <= hi]
    return {
        "workload": run.cell.name, "device": device, "seed": seed,
        "window_s": tr.window_s(t), "busy_s": tr.busy_s(t),
        "fused_cwp_s": tr.op_time_s(t, pick),
        "kernel_events": dict(Counter(
            tr.short(o.name) for ops in t.ops.values() for o in ops
            if pick(o) and lo <= o.start and o.end <= hi)),
        "images": run.done_in_window, "engine": run.engine,
        "metrics": {m["name"]: mod.read(run)
                    for m, mod in run.cell.metrics[1]},
        "frontend_step": [len(step_spans), sum(step_spans) / 1e9],
        "top_ops": tr.top_ops(t, 3), "idle_gaps": tr.idle_gaps(t),
        "program": pt.summary(t), "hold": hold}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True,
                    help="path of the fixture, without its suffixes")
    args = ap.parse_args(argv)

    cell = harness.resolve(args.workload)
    import jax
    marks = {"start": harness.clock()}
    dev = jax.devices()[0]
    marks["init"] = harness.clock()
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found {dev.platform}")
    peak = json.loads((HERE.parent / "peaks.json").read_text()
                      )["devices"][dev.device_kind]
    _, pool, engine, frontend = harness.setup(cell, args.seed, marks)
    due = harness.open_due(cell, args.seconds, args.seed) \
        if cell.traffic["loop"] == "open" else None
    trace_dir = tempfile.mkdtemp(prefix="onchip_fixture_")
    spans = Spans(engine.stats)
    got = harness.measure(cell, engine, frontend, pool, args.seconds, due,
                          spans, trace_dir)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    xplane = out.with_name(out.name + ".xplane.pb")
    shutil.copyfile(tr.newest_xplane(trace_dir), xplane)
    shutil.rmtree(trace_dir, ignore_errors=True)

    win = got.win
    run = harness.Run(cell, args.seconds, 0.0, np.array(win.due),
                      np.array(win.dispatch), np.array(win.finish),
                      got.done_in_window, got.engine,
                      cell.family.stages(cell.config),
                      cell.family.flops_per_image(cell.config), peak,
                      got.compiles, pt.load(str(xplane)))
    numbers = note(run, args.seed, dev.device_kind, spans.hold)
    out.with_name(out.name + ".json").write_text(
        json.dumps(numbers, indent=1) + "\n")
    print(json.dumps(numbers["metrics"]), flush=True)


if __name__ == "__main__":
    main()
