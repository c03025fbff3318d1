"""Spans and counters of the simulator's hot path.

A span is a ``jax.profiler.TraceAnnotation``: with a profiler running
(``jax.profiler.trace(dir)``) it lands in the same ``.xplane.pb`` as the
device's ``XLA Ops``/``XLA Modules`` events, on one clock, so each idle
gap of the chip can be put down to the host work that was running.
With no profiler running a span costs about a microsecond.  Spans are
placed once per call of a phase, never per request (the tree is in
``docs/engine.md``, "Observability").

Counters are plain integers summed per process; the code that owns one
adds to it once per call, never inside a per-request loop.
``counters()`` after a ``run_grid`` gives the totals of that process:
sweep-farm worker processes (``run_grid(workers=N)``) keep their own.
"""
from __future__ import annotations

import threading
from typing import Dict

import jax

_COUNTERS: Dict[str, int] = {}
_LOCK = threading.Lock()


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span called ``name``, for a ``with`` block."""
    return jax.profiler.TraceAnnotation(name)


def add(name: str, value: int) -> None:
    """Add ``value`` to the counter ``name``."""
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + value


def counters() -> Dict[str, int]:
    """A copy of every counter of this process."""
    with _LOCK:
        return dict(_COUNTERS)


def reset() -> None:
    """Drop every counter (tests)."""
    with _LOCK:
        _COUNTERS.clear()
