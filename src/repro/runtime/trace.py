"""The program's host spans: one named interval per layer boundary.

``with span("explore.fused"):`` records an ``rcim.explore.fused`` event in
the JAX profiler's trace (`jax.profiler.TraceAnnotation`), on the host
line of the calling thread and on the same clock as the device's
operations, so a reader of the trace can put device time and idle time
down to the layer that was running.  Keyword arguments become the
event's stats; keep them to attributes and ``nbytes`` sums.  A value
known only at the end of the span goes in through ``set_metadata`` on
the object the ``with`` statement binds.

Outside a profiler session a span costs about a microsecond and records
nothing.  In a process that has not imported jax (the python backend's
spawn workers) it never imports it.  A span belongs around a whole
phase or a device call, never inside a per-item python loop.
"""

from __future__ import annotations

import sys

#: Every span's name starts with this.
PREFIX = "rcim."


class _NullSpan:
    """What `span` returns when nothing is recording."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **args) -> None:
        pass


_NULL = _NullSpan()


def span(name: str, **args):
    """A context manager recording ``PREFIX + name`` with ``args`` while a
    profiler session is active."""
    jax = sys.modules.get("jax")
    if jax is None or not jax.profiler.TraceAnnotation.is_enabled():
        return _NULL
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
