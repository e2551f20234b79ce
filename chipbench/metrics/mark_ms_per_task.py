"""Runtime: milliseconds of one task's once-marker and node accounting after
its bundle is durable (span ``runtime.mark``)."""
from chipbench import span_totals


def read(r):
    return span_totals.ms_each(r, "runtime.mark")
