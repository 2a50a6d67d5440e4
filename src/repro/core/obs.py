"""Spans of the program on the profiler's clock.

``span(name)`` marks a stretch of the calling thread in a running
``jax.profiler`` trace, as ``jax.profiler.TraceAnnotation`` does: the span
lands in the profiler's host plane, on the same clock as the device
events, on the line of the thread that ran it.  With no trace running it
costs the annotation's one check.  Nothing is kept in memory and nothing
is exported: a span exists only inside a trace.

The cache package never imports JAX (the daemon, the process-driver
workers and the simulator run without it), so ``span`` looks JAX up
among the loaded modules on each call and is a no-op in a process that
has not imported it.

Span names are ``igt.<layer>.<what>``; docs/API.md lists them.
"""
from __future__ import annotations

import contextlib
import sys

_OFF = contextlib.nullcontext()


def span(name: str):
    """Context manager: the span ``name`` on this thread while a
    profiler trace runs."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return _OFF
    return profiler.TraceAnnotation(name)
