"""Reduce a JAX profiler trace to device busy time, top ops and idle gaps.

``device_events`` reads the ``.xplane.pb`` file that
``jax.profiler.start_trace``/``stop_trace`` write and returns, for each
chip, its device operations as ``(name, start_ns, duration_ns)``.
``reduce`` turns those into:

- ``busy_s``: the union of the intervals in which an operation ran on a
  chip, averaged over the chips;
- ``window_s``: the length of the traced window on the host clock;
- ``device_ops``: the ten operations with the most device time (seconds
  per chip);
- ``idle_gaps``: the ten longest gaps between operations on chip 0,
  each named after the operation that ran before it.

The program has no spans of its own yet, so a gap is named by the device
operation it follows, not by what the host was doing in it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

Event = Tuple[str, int, int]  # (name, start_ns, duration_ns)

_DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
# the line of a TPU plane that holds one event per executed HLO op
_OPS_LINE = "XLA Ops"
TOP = 10


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def _op_name(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def device_events(path: str, chips: int) -> Dict[int, List[Event]]:
    """Device operations of chips ``0 .. chips-1`` in an xplane file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: Dict[int, List[Event]] = {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) >= chips:
            continue
        for line in plane.lines:
            if line.name == _OPS_LINE:
                out[int(m.group(1))] = [(_op_name(e.name), int(e.start_ns),
                                         int(e.duration_ns))
                                        for e in line.events]
    return out


def _merge(events: List[Event]) -> List[Tuple[int, int, str]]:
    """Union of event intervals as (start, end, name of the last op)."""
    merged: List[Tuple[int, int, str]] = []
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            s, e, n = merged[-1]
            merged[-1] = (s, max(e, end), name if end >= e else n)
        else:
            merged.append((start, end, name))
    return merged


def reduce(events: Dict[int, List[Event]], window_s: float) -> dict:
    if not events or not any(events.values()):
        raise RuntimeError("the trace holds no device operation")
    chips = len(events)
    busy = 0.0
    per_op: Dict[str, float] = {}
    for evs in events.values():
        busy += sum(e - s for s, e, _ in _merge(evs)) / 1e9
        for name, _, dur in evs:
            per_op[name] = per_op.get(name, 0.0) + dur / 1e9
    merged = _merge(events[min(events)])
    gaps = [(f"after {merged[i][2]}", (merged[i + 1][0] - merged[i][1]) / 1e9)
            for i in range(len(merged) - 1)]
    return {
        "busy_s": busy / chips,
        "window_s": window_s,
        "device_ops": sorted(([n, t / chips] for n, t in per_op.items()),
                             key=lambda x: -x[1])[:TOP],
        "idle_gaps": [list(g) for g in sorted(gaps, key=lambda g: -g[1])[:TOP]],
    }
