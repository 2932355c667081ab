"""Host spans of the program, on the profiler's clock.

``span(name)`` around a piece of host work makes it an event of the
profiler's own trace, named ``dlrover:<name>``: a
``jax.profiler.TraceAnnotation``, which lands in the ``/host:CPU``
plane of the ``.xplane.pb`` beside the device's lines, so an idle gap
of the chip can be put down to what the host was doing in it. With no
profiling session open an annotation records nothing and costs about a
microsecond; sessions are opened by the executor's profiling window
(scheduled through ``trace_dir``, or on demand through the
``profile_signal`` knob: docs/observability.md).

A process that has not loaded JAX (the master, the agent, a
benchmark's runner) gets a no-op: this module never imports it.
"""

from __future__ import annotations

import contextlib
import sys

from dlrover_tpu.common.config import get_context

SPAN_PREFIX = "dlrover:"

_NO_SPAN = contextlib.nullcontext()


def span(name: str, **args):
    """A context manager around one piece of host work. ``args`` become
    the annotation's own arguments, which the trace shows as a
    ``#key=value#`` tail of the event's name."""
    if not getattr(get_context(), "telemetry_enabled", True):
        return _NO_SPAN
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return _NO_SPAN
    return profiler.TraceAnnotation(SPAN_PREFIX + name, **args)
