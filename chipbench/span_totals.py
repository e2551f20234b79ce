"""The program's span totals over the window, from a study driver's
readings.

``ExecutionEngine.stats()`` carries the process's span totals
(``repro.core.spans``) as ``span_s.<name>`` and ``span_n.<name>``, and the
study driver diffs the engine's stats across the window into
``readings["engine"]``.  A program without spans has no such keys, and
every reader here then returns None.
"""


def window(r, name):
    """(count, seconds) of span ``name`` inside the window, or None."""
    e = r.get("engine") or {}
    n, s = e.get(f"span_n.{name}"), e.get(f"span_s.{name}")
    return None if n is None or s is None else (n, s)


def ms_each(r, name):
    """Mean milliseconds of one ``name`` span (or counted wait) in the
    window, or None where there was none."""
    w = window(r, name)
    return 1000.0 * w[1] / w[0] if w and w[0] else None
