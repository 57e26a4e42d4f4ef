"""The program's one span primitive: named host spans on the profiler's
clock (DESIGN.md §11).

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler session is active (``jax.profiler.start_trace``, or
``launch/serve.py --profile-dir``) it records an event on the host plane
of the trace, on the same clock as the device's operations, so a reader
can put each stretch of device idle time down to what the host was doing
in it. With no session active it records nothing and costs one
enter/exit of a native object, so spans stay on in serving.

Names are ``<layer>.<part>`` (``frontend.step``, ``vision.fetch``,
``boot.compile``). Spans nest on the thread that opens them, so the
enclosing span is a span's parent; ``meta`` becomes the event's stats
(``bucket=8``).
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["span"]


def span(name: str, **meta) -> TraceAnnotation:
    """A context manager recording ``name`` (with ``meta``) as a host
    span while a profiler session is active."""
    return TraceAnnotation(name, **meta)
