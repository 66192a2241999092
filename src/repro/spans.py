"""Named host spans on the profiler's clock: the program's layer boundaries.

``span(name, **args)`` is the one way the program marks a layer.  It is a
``jax.profiler.TraceAnnotation``, so under ``jax.profiler.trace`` the span
lands in the same ``.xplane.pb`` as the device's operations, on the same
clock, its name clean and ``args`` kept as the event's stats.  The profiler
being on is the only switch: off, a span records nothing.

A process that has not imported JAX cannot be profiling, so there a span is
a null context and the numpy-only paths (the simulation) never import JAX.

Spans go around synchronous code only, never across a ``yield`` of an
event-loop task (the loop's other tasks would run inside them), and never
inside a loop over planes, the nodes of a plane, samples or events.
"""
from __future__ import annotations

import contextlib
import sys

_OFF = contextlib.nullcontext()


def span(name: str, **args):
    """A context manager that records ``name`` (with ``args``) while the
    profiler runs."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _OFF
    return jax.profiler.TraceAnnotation(name, **args)
