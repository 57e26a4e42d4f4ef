"""Time-to-ready attribution for plan boot (DESIGN.md §12).

A serving replica's cold start is a fixed pipeline — trace → fuse →
place → tune → compile → first dispatch — and the whole point of the
plan artifact store is to drive the first four phases to **zero**. This
module is the measuring tape: an ambient ``WarmupReport`` (contextvar,
so threaded engines and jit trace-time code both see it) that the
compile pipeline writes into through ``phase(name)`` blocks.

Outside a ``collect_warmup()`` block every ``phase`` touches no ambient
state, so the hooks in ``repro.graph.plan`` and ``repro.serve.vision``
cost nothing on the hot path. Every ``phase`` also opens the program's
span ``boot.<name>`` (``repro.spans``), so a profiler trace of a boot
(``launch/serve.py --profile-dir``) shows its phases.

The report also keeps which operand layout each window-conv kernel
took (``note_layout``, called by ``kernels/conv_window`` as the plan is
traced, keyed by the kernel's name ``conv_window.s<i>``): a boot that
loads AOT executables traces nothing and records none.

``launch/serve.py --warmup-report`` prints the breakdown; a replica
booted with ``--plan-artifact`` must show ``trace``/``fuse``/``place``/
``tune`` at 0 calls — that is the asserted "zero-compilation boot".

This module imports only the stdlib and ``repro.spans``: it sits below
the graph compiler in the import graph (``repro.graph.plan`` imports
it), while the rest of ``repro.artifact`` sits above.
"""
from __future__ import annotations

import contextlib
import contextvars
import time
from dataclasses import dataclass, field

from repro.spans import span

__all__ = ["PHASES", "WarmupReport", "collect_warmup", "phase",
           "current_report", "note_layout"]

# the canonical cold-start pipeline, in execution order. "fold" is the
# bind-time constant fold (weight quantization, batch norm folded into
# its conv). "artifact" is the phase the store adds (manifest + payload
# load, AOT deserialize); it replaces the first six when a replica boots
# from an artifact.
PHASES = ("trace", "fuse", "place", "fold", "tune", "compile", "artifact",
          "first_dispatch")


@dataclass
class WarmupReport:
    """Per-phase wall seconds + call counts for one boot, and the layout
    each traced window-conv kernel took."""

    seconds: dict[str, float] = field(default_factory=dict)
    counts: dict[str, int] = field(default_factory=dict)
    total_s: float = 0.0
    # kernel name (``conv_window.s<i>``) -> operand layout
    layouts: dict[str, str] = field(default_factory=dict)

    def add(self, name: str, dt: float) -> None:
        self.seconds[name] = self.seconds.get(name, 0.0) + dt
        self.counts[name] = self.counts.get(name, 0) + 1

    def phase_s(self, name: str) -> float:
        return self.seconds.get(name, 0.0)

    def phase_calls(self, name: str) -> int:
        return self.counts.get(name, 0)

    def zero_compile(self) -> bool:
        """True when no derivation work ran: the artifact-boot invariant
        (trace/fuse/place/tune never invoked)."""
        return all(self.phase_calls(p) == 0
                   for p in ("trace", "fuse", "place", "tune"))

    def pretty(self) -> str:
        lines = ["time-to-ready breakdown:"]
        for name in PHASES:
            lines.append(f"  {name:<14} {self.phase_s(name) * 1e3:9.1f} ms"
                         f"  ({self.phase_calls(name)} calls)")
        accounted = sum(self.seconds.values())
        lines.append(f"  {'other':<14} "
                     f"{max(self.total_s - accounted, 0.0) * 1e3:9.1f} ms")
        lines.append(f"  {'total':<14} {self.total_s * 1e3:9.1f} ms")
        counts: dict[str, dict[str, int]] = {}
        for kernel, layout in self.layouts.items():
            per = counts.setdefault(kernel.split(".")[0], {})
            per[layout] = per.get(layout, 0) + 1
        for family, per in sorted(counts.items()):
            lines.append(f"{family} layouts: " + ", ".join(
                f"{layout} {n}" for layout, n in sorted(per.items())))
        return "\n".join(lines)


_ACTIVE: contextvars.ContextVar[WarmupReport | None] = \
    contextvars.ContextVar("repro_warmup_report", default=None)


def current_report() -> WarmupReport | None:
    return _ACTIVE.get()


@contextlib.contextmanager
def collect_warmup():
    """Collect phase timings for the dynamic extent of the block. Nested
    collectors shadow the outer one (each boot gets its own report)."""
    report = WarmupReport()
    token = _ACTIVE.set(report)
    t0 = time.perf_counter()
    try:
        yield report
    finally:
        report.total_s = time.perf_counter() - t0
        _ACTIVE.reset(token)


@contextlib.contextmanager
def phase(name: str):
    """Attribute the block's wall time to ``name`` in the ambient report
    (none when no ``collect_warmup`` is active), inside the span
    ``boot.<name>``."""
    report = _ACTIVE.get()
    with span(f"boot.{name}"):
        if report is None:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            report.add(name, time.perf_counter() - t0)


def note_layout(kernel: str, layout: str) -> None:
    """Record that the kernel named ``kernel`` took ``layout`` (in the
    ambient report; nothing outside a ``collect_warmup`` block)."""
    report = _ACTIVE.get()
    if report is not None:
        report.layouts[kernel] = layout
