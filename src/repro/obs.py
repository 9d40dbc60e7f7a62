"""Spans on the profiler's clock, at the program's layer boundaries.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler runs, it lands on the host plane of the same ``xplane.pb`` as the
device operations, on the same clock, with ``stats`` attached to the event;
with no profiler running it costs about a microsecond and records nothing.
A span's stats may be completed before it closes with ``set_metadata``.

Names start with ``train.``, ``outer.``, ``serve.`` or ``jax.``.  Stats come
from host mirrors only (slot tables, the page allocator, the queue, host
step counters): a stat never reads a device array, and a span never waits
for the device.  A stat that costs more than O(1) is computed only under
:func:`enabled`.

Importing this module also registers one ``jax.monitoring`` listener: every
backend compile that finishes while a profiler runs leaves a zero-length
``jax.compile`` marker with the compile's seconds as stat ``secs``, so the
device trace shows which step recompiled.  ``mark(name, **stats)`` leaves
such a marker: the flash-attention kernel leaves ``jax.flash_plan`` (its
tiles and live tile pairs) each time it is traced under a profiler.

These spans are the timing view of the boundaries whose events the
training loop's JSONL stream and the outer-program pool's ``recompile``
events already log; they log nothing of their own.
"""

from __future__ import annotations

import jax
from jax.profiler import TraceAnnotation

__all__ = ["span", "enabled", "mark"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def span(name: str, **stats) -> TraceAnnotation:
    """A host span ``name`` with ``stats``, used as a context manager."""
    return TraceAnnotation(name, **stats)


def enabled() -> bool:
    """Whether a profiler is running, i.e. whether spans are recorded."""
    return TraceAnnotation.is_enabled()


def mark(name: str, **stats) -> None:
    """A zero-length marker ``name`` with ``stats``, left only while a
    profiler runs."""
    if enabled():
        with TraceAnnotation(name, **stats):
            pass


def _mark_compile(event: str, secs: float, **_kwargs) -> None:
    if event == COMPILE_EVENT:
        mark("jax.compile", secs=float(secs))


jax.monitoring.register_event_duration_secs_listener(_mark_compile)
