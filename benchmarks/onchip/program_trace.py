"""Reduce the program's own spans in a profiler trace: what the serving
step spends its host time on, and which part of it keeps the device idle.

``trace_reduce.load`` keeps a trace's device operations and the
benchmark's host spans; ``load`` here keeps, besides those, the
program's spans (``repro.spans``: ``<layer>.<part>`` names of the layers
in ``PROGRAM_SPAN``, opened on the one serving thread, so they nest) and
each device's executable runs (its ``XLA Modules`` line). Over the
window:

* ``span_times``: a program span's count, total time and self time (its
  time less the part its child spans cover);
* ``module_times``: the durations of the executable runs a predicate
  picks;
* ``idle_gaps``: the device's idle time put down to the innermost
  program span it fell in, and where no program span covers it, to the
  benchmark's host span, as ``trace_reduce.idle_gaps`` does; on a trace
  without program spans the two agree.

    python3 benchmarks/onchip/program_trace.py TRACE

prints these for a recorded ``.xplane.pb`` (or the newest one under a
directory, such as ``launch/serve.py --profile-dir``'s). Its window is
the benchmark's ``window`` span where there is one, else the stretch
from the first program span to the last.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

import trace_reduce as tr

PROGRAM_SPAN = re.compile(r"(frontend|vision|boot)\.[a-z_]+")
MODULES_LINE = "XLA Modules"
SERVING_SPANS = ("frontend.step", "vision.step", "vision.place",
                 "vision.launch", "vision.fetch", "vision.deliver")


@dataclass
class ProgramTrace(tr.Trace):
    # the program's spans, (name, start ns, end ns)
    program: list[tuple[str, int, int]] = field(default_factory=list)
    # device plane -> executable runs
    modules: dict[str, list[tr.Op]] = field(default_factory=dict)

    def window(self) -> tuple[int, int]:
        if any(name == "window" for name, _, _ in self.spans) or \
                not self.program:
            return super().window()
        return (min(s for _, s, _ in self.program),
                max(e for _, _, e in self.program))


def load(path: str) -> ProgramTrace:
    """The trace at ``path`` (a ``.xplane.pb``, or the newest one under a
    directory) with the program's spans and executable runs."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = tr.newest_xplane(path)
    base = tr.load(path)
    program, modules = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name in base.ops:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = [
                        tr.Op(ev.name, int(ev.start_ns),
                              int(ev.start_ns + ev.duration_ns))
                        for ev in line.events]
        elif not plane.name.startswith(tr.DEVICE_PREFIX):
            program += [(ev.name, int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns))
                        for line in plane.lines for ev in line.events
                        if PROGRAM_SPAN.fullmatch(ev.name)]
    return ProgramTrace(base.ops, base.spans, program, modules)


def _in_window(trace: ProgramTrace) -> list[tuple[int, int, str]]:
    """The program's spans clipped to the window, as (start, end, name),
    each parent before the children that start with it."""
    lo, hi = trace.window()
    return sorted(((max(s, lo), min(e, hi), name)
                   for name, s, e in trace.program if e > lo and s < hi),
                  key=lambda sp: (sp[0], -sp[1]))


def span_times(trace: ProgramTrace, name: str) -> tuple[int, float, float]:
    """The program spans called ``name`` inside the window: how many,
    their summed duration and their summed self time (each one's
    duration less the part its child spans cover), in seconds."""
    spans = _in_window(trace)
    starts = [s for s, _, _ in spans]
    count = total = inner = 0
    for i, (s, e, nm) in enumerate(spans):
        if nm != name:
            continue
        count += 1
        total += e - s
        # nested on one thread: what starts inside it and ends by its
        # end is a descendant, and the children's union covers them all
        inside = spans[i + 1:bisect.bisect_left(starts, e)]
        inner += sum(ce - cs for cs, ce in
                     tr._union((cs, ce) for cs, ce, _ in inside if ce <= e))
    return count, total / 1e9, (total - inner) / 1e9


def module_times(trace: ProgramTrace, pick) -> list[float]:
    """Durations in seconds of the executable runs (``XLA Modules``
    events) inside the window that ``pick(op)`` accepts, over all
    devices."""
    lo, hi = trace.window()
    return [(e - s) / 1e9 for runs in trace.modules.values()
            for s, e in tr._clipped(((o.start, o.end) for o in runs
                                     if pick(o)), lo, hi)]


def _innermost(trace: ProgramTrace) -> list[tuple[int, int, str]]:
    """The stretches of the window that program spans cover, disjoint and
    in order, each as (start, end, name of the innermost span over it)."""
    out: list[tuple[int, int, str]] = []
    stack: list[tuple[int, str]] = []     # (end, name) of the open spans
    t = 0

    def close_until(s):
        nonlocal t
        while stack and stack[-1][0] <= s:
            end, outer = stack.pop()
            if end > t:
                out.append((t, end, outer))
                t = end

    for s, e, name in _in_window(trace):
        close_until(s)
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        t = s
        stack.append((e, name))
    close_until(float("inf"))
    return out


def idle_gaps(trace: ProgramTrace, n: int = 10) -> list[list]:
    """Idle time of the first device in the window, summed per name:
    ``[[name, seconds], ...]``, largest first. Idle time under a program
    span goes to the innermost one; the rest of each gap is split over
    the benchmark's host spans it overlaps (the rest of that is
    ``other``)."""
    lo, hi = trace.window()
    ops = trace.ops[min(trace.ops)]
    busy = tr._union(tr._clipped(((o.start, o.end) for o in ops), lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    # the benchmark's host spans follow one another on one thread, so
    # sorted by start they are sorted by end too
    host = sorted((s, e, name) for name, s, e in trace.spans
                  if name != "window")
    ends = [e for _, e, _ in host]
    by_name: dict[str, int] = defaultdict(int)

    def by_host(gs, ge):
        covered = []
        i = bisect.bisect_right(ends, gs)
        while i < len(host) and host[i][0] < ge:
            s, e, name = host[i]
            by_name[name] += min(e, ge) - max(s, gs)
            covered.append((max(s, gs), min(e, ge)))
            i += 1
        by_name["other"] += (ge - gs) - sum(
            e - s for s, e in tr._union(covered))

    inner = _innermost(trace)
    inner_ends = [e for _, e, _ in inner]
    for gs, ge in gaps:
        t = gs
        i = bisect.bisect_right(inner_ends, gs)
        while i < len(inner) and inner[i][0] < ge:
            s, e, name = inner[i]
            if s > t:
                by_host(t, s)
            by_name[name] += min(e, ge) - max(s, t)
            t = min(e, ge)
            i += 1
        if t < ge:
            by_host(t, ge)
    ranked = sorted(((k, v) for k, v in by_name.items() if v > 0),
                    key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def summary(trace: ProgramTrace) -> dict:
    """Each serving span's count, total and self time in seconds, the
    bucket executables' runs (count and mean seconds) and the idle
    breakdown."""
    runs = module_times(trace, lambda op: op.name.startswith("jit_vision_b"))
    return {"window_s": tr.window_s(trace),
            "busy_s": tr.busy_s(trace),
            "spans": {name: list(span_times(trace, name))
                      for name in SERVING_SPANS},
            "vision_modules": [len(runs),
                               sum(runs) / len(runs) if runs else None],
            "idle_gaps": idle_gaps(trace)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="a .xplane.pb, or a directory holding one")
    args = ap.parse_args(argv)
    print(json.dumps(summary(load(args.trace)), indent=1))


if __name__ == "__main__":
    main()
