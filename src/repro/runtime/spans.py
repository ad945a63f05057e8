"""Host spans on the profiler's clock: the program's one tracing system.

``span("engine.prefill", rids=[3, 7])`` is a ``jax.profiler.TraceAnnotation``
named ``repro.engine.prefill``.  It writes into the profiler's own trace, so
it shares a clock with the device's operations, and it costs about a
microsecond when no trace is being taken (its metadata is formatted only
while one is).  :func:`trace_gc` adds a ``repro.host.gc`` span around every
collection of Python's garbage collector, until :func:`untrace_gc`.
"""

from __future__ import annotations

import gc
from typing import List

import jax

PREFIX = "repro."


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A context manager that records ``repro.<name>`` while tracing."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


_open: List[jax.profiler.TraceAnnotation] = []


def _on_gc(phase: str, info: dict):
    if phase == "start":
        s = span("host.gc", generation=info["generation"])
        s.__enter__()
        _open.append(s)
    elif _open:
        _open.pop().__exit__(None, None, None)


def trace_gc():
    """Open a ``repro.host.gc`` span at the start of every garbage
    collection and close it at its end."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def untrace_gc():
    """Stop :func:`trace_gc`'s spans."""
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
