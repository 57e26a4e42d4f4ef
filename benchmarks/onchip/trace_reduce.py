"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

``load`` keeps two things of a trace: each device's operations (the
``XLA Ops`` line of every ``/device:TPU:<i>`` plane) and the benchmark's
own host spans (``jax.profiler.TraceAnnotation`` names in ``SPANS``).
Everything else is arithmetic over intervals inside the ``window`` span:

* ``busy_s``: the union of a device's operation intervals, averaged over
  devices; the device is idle for the rest of the window;
* ``op_time_s``: the summed duration of the operations a predicate picks
  (a kernel's events), averaged over devices;
* ``top_ops`` and ``idle_gaps``: the breakdown of a traced run, the
  operations that took most time and the idle time labelled by the host
  span it fell in.
"""
from __future__ import annotations

import bisect
import glob
import os
import warnings
from collections import defaultdict
from dataclasses import dataclass

SPANS = ("window", "submit", "frontend_step", "wait_arrival")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclass
class Op:
    name: str
    start: int          # ns
    end: int            # ns
    detail: str = ""    # the event's string stats, for matching by name


@dataclass
class Trace:
    ops: dict[str, list[Op]]                # device plane -> operations
    spans: list[tuple[str, int, int]]       # (name, start ns, end ns)

    def window(self) -> tuple[int, int]:
        for name, s, e in self.spans:
            if name == "window":
                return s, e
        raise ValueError("trace holds no 'window' span")


def newest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def short(name: str) -> str:
    """An operation's HLO instruction name (``%copy.4``) without the rest
    of its text (``= f32[...] copy(...)``)."""
    return name.split(" = ", 1)[0]


def _string_stats(event) -> str:
    with warnings.catch_warnings():     # jaxlib's stats type warns when
        warnings.simplefilter("ignore")  # it is iterated
        return " ".join(str(v) for _, v in event.stats if isinstance(v, str))


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: dict[str, list[Op]] = {}
    spans = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX) and \
                plane.name[len(DEVICE_PREFIX):].isdigit():
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops[plane.name] = [
                    Op(ev.name, int(ev.start_ns),
                       int(ev.start_ns + ev.duration_ns), _string_stats(ev))
                    for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns)))
    if not ops:
        raise ValueError(f"{path}: no {OPS_LINE!r} line on any "
                         f"{DEVICE_PREFIX}<i> plane")
    return Trace(ops, spans)


def _clipped(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window_s(trace: Trace) -> float:
    s, e = trace.window()
    return (e - s) / 1e9


def busy_s(trace: Trace) -> float:
    lo, hi = trace.window()
    per_device = [
        sum(e - s for s, e in _union(_clipped(((o.start, o.end) for o in ops),
                                              lo, hi)))
        for ops in trace.ops.values()]
    return sum(per_device) / len(per_device) / 1e9


def op_time_s(trace: Trace, pick) -> float:
    """Summed duration inside the window of the operations ``pick(op)``
    accepts, averaged over devices."""
    lo, hi = trace.window()
    total = sum(e - s for ops in trace.ops.values()
                for s, e in _clipped(((o.start, o.end) for o in ops
                                      if pick(o)), lo, hi))
    return total / len(trace.ops) / 1e9


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` operations (by ``short`` name) that took most device
    time in the window, as ``[name, seconds]`` averaged over devices."""
    lo, hi = trace.window()
    by_name: dict[str, int] = defaultdict(int)
    for ops in trace.ops.values():
        for o in ops:
            for s, e in _clipped([(o.start, o.end)], lo, hi):
                by_name[short(o.name)] += e - s
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / len(trace.ops) / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """Idle time of the first device in the window, each gap split over
    the host spans it overlaps (the rest is ``other``), summed per span
    name: ``[[name, seconds], ...]``, largest first."""
    lo, hi = trace.window()
    ops = trace.ops[min(trace.ops)]
    busy = _union(_clipped(((o.start, o.end) for o in ops), lo, hi))
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
    for gs, ge in gaps:
        covered = []
        i = bisect.bisect_right(ends, gs)
        while i < len(host) and host[i][0] < ge:
            s, e, name = host[i]
            by_name[name] += min(e, ge) - max(s, gs)
            covered.append((max(s, gs), min(e, ge)))
            i += 1
        by_name["other"] += (ge - gs) - sum(e - s for s, e in _union(covered))
    ranked = sorted(((k, v) for k, v in by_name.items() if v > 0),
                    key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]
