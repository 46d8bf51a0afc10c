"""Spans at the layer boundaries of the serving path.

``span("engine.select")`` is a ``jax.profiler.TraceAnnotation`` named
``lazyvlm.engine.select``. While a ``jax.profiler`` trace is active it is
recorded on the trace's host plane, on the same clock as the device
planes of the same ``.xplane.pb``, so an idle stretch of the device can be
put down to the innermost span open at the time; with no trace active
nothing is recorded. Keyword metadata (``batch=3``) becomes the event's
stats; a value must not hold ``,``, ``=`` or ``#``, which the profiler's
encoding of metadata reserves.

The span also keeps its own wall time, ``seconds`` (a ``perf_counter``
pair), from which ``QueryStats.stage_seconds`` is filled::

    with span("engine.search") as s:
        ...
    stats.stage_seconds["entity_match"] = s.seconds
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

PREFIX = "lazyvlm."


class span:
    """Context manager: one profiler span, and its wall time in
    ``seconds`` once it has closed."""

    __slots__ = ("_annotation", "_t0", "seconds")

    def __init__(self, name: str, **meta):
        self._annotation = TraceAnnotation(PREFIX + name, **meta)
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
