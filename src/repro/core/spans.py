"""Named host spans: per-name totals in the process, and a place on the
JAX profiler's timeline.

``span(name, **ids)`` times a block with ``time.perf_counter`` and adds
its seconds and one count to the process's totals.  While a profiler
trace runs (``jax.profiler.start_trace``) the block is also a
``jax.profiler.TraceAnnotation``: it lands on its thread's line of the
trace's ``/host:CPU`` plane, on the same clock as the device operations,
with ``ids`` as the event's metadata (encoded into the name as
``name#batch=40#``).  There is no switch: the profiler decides whether a
timeline is recorded, and the totals are always kept.

Names are ``<layer>.<what>``.  The spans of one fused launch carry
``batch=<lowest sample id of the launch>`` and per-task spans carry
``task=<lowest sample id of the task>``, so a launch's spans share an id
in the trace.

``count(name, seconds, n)`` adds time that is not one thread's block,
such as the seconds n tasks waited in a buffer.  ``totals()`` returns a
snapshot ``{name: {"n": count, "s": seconds}}``; diff two snapshots to
read a window.

This module never imports JAX: a process that has not imported
``jax.profiler`` cannot be tracing, so its spans only count.
"""
from __future__ import annotations

import sys
import threading
import time
from typing import Dict

_LOCK = threading.Lock()
_TOTALS: Dict[str, list] = {}  # name -> [count, seconds]
_annotation = None  # jax.profiler.TraceAnnotation, once JAX is imported


def count(name: str, seconds: float, n: int = 1) -> None:
    """Add ``n`` counts and ``seconds`` to ``name``'s totals."""
    with _LOCK:
        t = _TOTALS.get(name)
        if t is None:
            _TOTALS[name] = [n, seconds]
        else:
            t[0] += n
            t[1] += seconds


def totals() -> Dict[str, Dict[str, float]]:
    """Snapshot of every name's count and seconds so far."""
    with _LOCK:
        return {k: {"n": n, "s": s} for k, (n, s) in _TOTALS.items()}


def _trace_annotation():
    global _annotation
    if _annotation is None:
        _annotation = getattr(sys.modules.get("jax.profiler"),
                              "TraceAnnotation", None)
    return _annotation


class span:
    """Context manager timing one block under ``name``; after the block,
    ``t0`` and ``t1`` hold its ``perf_counter`` bounds."""

    __slots__ = ("name", "ids", "t0", "t1", "_ann")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids
        self.t0 = self.t1 = 0.0
        self._ann = None

    def __enter__(self) -> "span":
        cls = _trace_annotation()
        if cls is not None:
            self._ann = cls(self.name, **self.ids)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        count(self.name, self.t1 - self.t0)
        return False
