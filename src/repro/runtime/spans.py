"""Named host spans on the profiler's clock.

``span("map.admit")`` marks a phase of the program as the host annotation
``union.map.admit``. Under ``jax.profiler`` the annotation is recorded in
the same trace as the device's operations, so a profile shows which phase
the host was in while the chip sat idle. With no profiler running an
annotation costs about a microsecond, so spans stay on; they are opened
per batch or per step, never per candidate.

This module imports no jax: ``repro.core`` opens spans on its numpy path
too, where sweep worker processes must stay jax-free. A span is recorded
only where jax is already imported; elsewhere it is a shared null context.

``span(name, (stats, "admit_s"))`` also adds the seconds spent inside it
to ``stats.admit_s``, so a counter and a span that time the same block
are one object.
"""

from __future__ import annotations

import sys
from contextlib import nullcontext
from time import perf_counter
from typing import Optional, Tuple

PREFIX = "union."

_NULL = nullcontext()


def _profiler():
    jax = sys.modules.get("jax")
    return None if jax is None else jax.profiler


class _Timed:
    """An annotation (or none) that also adds its seconds to a field."""

    __slots__ = ("_ann", "_obj", "_field", "_t0")

    def __init__(self, ann, obj, field: str) -> None:
        self._ann, self._obj, self._field = ann, obj, field

    def __enter__(self) -> "_Timed":
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        setattr(self._obj, self._field, getattr(self._obj, self._field) + perf_counter() - self._t0)
        if self._ann is not None:
            self._ann.__exit__(*exc)


def span(name: str, timer: Optional[Tuple[object, str]] = None):
    """The host span ``union.<name>``; with ``timer=(obj, field)`` the
    seconds inside it are added to ``obj.field``."""
    prof = _profiler()
    ann = None if prof is None else prof.TraceAnnotation(PREFIX + name)
    if timer is not None:
        return _Timed(ann, *timer)
    return _NULL if ann is None else ann


def step_span(name: str, step: int):
    """The step marker ``union.<name>`` of step ``step``: a profile viewer
    shows the steps of a loop."""
    prof = _profiler()
    return _NULL if prof is None else prof.StepTraceAnnotation(PREFIX + name, step_num=step)
