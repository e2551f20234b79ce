"""Name the device's idle gaps by the program spans that cover them.

The program times each layer of its study path with ``repro.core.spans``.
While the profiler runs, each span is also an event on its thread's line
of the trace's ``/host:CPU`` plane, on the same clock as the device
operations, named ``<name>#<ids>#`` (the part after ``#`` is metadata).

``host_spans`` reads those events, one list per host line.
``reduce_with_spans`` returns what ``trace.reduce`` returns, with each of
the ten longest idle gaps on chip 0 named by the program span that covers
most of it, and two more numbers: ``idle_gap_s``, the seconds between
device operations on chip 0, and ``idle_attributed_s``, the part of them
that some program span covers.

A span is charged with its self time: the part of its interval that no
span nested inside it on the same thread covers, so that a writer's
``engine.write`` gives way to the ``bundler.write`` calls inside it.  A
gap is named by the work span that covers most of it, with that share:
``after dynamic_slice.1: bundler.write 88%``.  Spans that only wait on
another layer (``WAITS``) name a gap only where no work span covers any
of it, and a gap that no span covers keeps its ``after <op>`` name.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

from chipbench import trace as T

# the program's spans on the study path; a span that waits on another
# layer is in WAITS
STUDY_SPANS = ("worker.lease", "worker.ack", "worker.wait",
               "engine.dispatch", "engine.handoff", "engine.write",
               "executor.launch", "executor.host_sync", "bundler.write",
               "runtime.mark")
WAITS = frozenset({"worker.wait", "engine.handoff"})
_HOST_PLANE = "/host:CPU"

Segment = Tuple[str, int, int]  # (name, start_ns, end_ns)


def host_spans(path: str, names: Iterable[str] = STUDY_SPANS
               ) -> List[List[T.Event]]:
    """The named program spans of an xplane file, one list per host line
    (thread), as ``(name, start_ns, duration_ns)``."""
    from jax.profiler import ProfileData
    want = set(names)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != _HOST_PLANE:
            continue
        for line in plane.lines:
            evs = [(name, int(e.start_ns), int(e.duration_ns))
                   for e in line.events
                   for name in (e.name.split("#", 1)[0],) if name in want]
            if evs:
                out.append(evs)
    return out


def self_segments(line: List[T.Event]) -> List[Segment]:
    """One thread's spans cut into pieces, each piece named by the
    innermost span open in it (spans of one thread nest)."""
    segs: List[Segment] = []
    stack: List[Tuple[str, int]] = []  # (name, end) of the open spans
    cursor = 0
    for name, start, dur in sorted(line, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            n, end = stack.pop()
            segs.append((n, cursor, end))
            cursor = end
        if stack:
            segs.append((stack[-1][0], cursor, start))
        stack.append((name, start + dur))
        cursor = start
    while stack:
        n, end = stack.pop()
        segs.append((n, cursor, end))
        cursor = end
    return [s for s in segs if s[2] > s[1]]


def _covered(intervals: List[Tuple[int, int]], lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) that the union of ``intervals`` covers."""
    total, reach = 0, lo
    for s, e in sorted(intervals):
        s, e = max(s, reach), min(e, hi)
        if e > s:
            total += e - s
            reach = e
    return total


def name_gap(lo: int, hi: int, segs: List[Segment]) -> Tuple[str, float]:
    """The span name that covers most of [lo, hi) and its share, work
    spans before ``WAITS``; ``("", 0.0)`` when no span touches it."""
    by_name: Dict[str, List[Tuple[int, int]]] = {}
    for name, s, e in segs:
        if s < hi and e > lo:
            by_name.setdefault(name, []).append((s, e))
    cover = {n: _covered(iv, lo, hi) for n, iv in by_name.items()}
    for pool in ([n for n in cover if n not in WAITS],
                 [n for n in cover if n in WAITS]):
        pool = [n for n in pool if cover[n] > 0]
        if pool:
            best = max(pool, key=lambda n: (cover[n], n))
            return best, cover[best] / (hi - lo)
    return "", 0.0


def reduce_with_spans(events: Dict[int, List[T.Event]], window_s: float,
                      lines: List[List[T.Event]]) -> dict:
    """``trace.reduce`` with the idle gaps of chip 0 named by spans, plus
    ``idle_gap_s`` and ``idle_attributed_s``."""
    out = T.reduce(events, window_s)
    segs = [s for line in lines for s in self_segments(line)]
    merged = T._merge(events[min(events)])
    gaps = []
    gap_ns = attributed_ns = 0
    for (_, end, op), (start, _, _) in zip(merged, merged[1:]):
        gap_ns += start - end
        attributed_ns += _covered([(s, e) for _, s, e in segs], end, start)
        name, share = name_gap(end, start, segs)
        label = f"after {op}" + (f": {name} {100 * share:.0f}%" if name
                                 else "")
        gaps.append([label, (start - end) / 1e9])
    out["idle_gaps"] = sorted(gaps, key=lambda g: -g[1])[:T.TOP]
    out["idle_gap_s"] = gap_ns / 1e9
    out["idle_attributed_s"] = attributed_ns / 1e9
    return out
