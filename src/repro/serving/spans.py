"""Host spans of the serving loop, in the profiler's own trace.

A span is a ``jax.profiler.TraceAnnotation``: with no profiler session it is
an inert TraceMe; inside one it lands in the trace's host plane, on the
calling thread's line and on the device ops' clock. Metadata travels as the
annotation's keyword arguments (event stats), never in the name, so a span's
name is stable. Given ``counters`` and ``key``, a span also adds its elapsed
``time.perf_counter_ns()`` to ``counters[key]``, traced or not.
"""
from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation


@contextlib.contextmanager
def span(name: str, counters: Optional[dict] = None, key: str = "", *,
         step_num: Optional[int] = None, **meta) -> Iterator[TraceAnnotation]:
    """``with span("engine.sync", self.counters, "sync_ns"): ...``; yields the
    annotation, whose ``set_metadata(**kw)`` adds stats known only inside.
    ``step_num`` makes it a step annotation (``StepTraceAnnotation``)."""
    t0 = time.perf_counter_ns()
    ann = (TraceAnnotation(name, **meta) if step_num is None
           else StepTraceAnnotation(name, step_num=step_num, **meta))
    try:
        with ann:
            yield ann
    finally:
        if counters is not None:
            counters[key] += time.perf_counter_ns() - t0
