"""EnsembleExecutor: milliseconds of one fused launch's host side, from padding
the rows to the dispatch of the slices that drop the padding (span
``executor.launch``)."""
from chipbench import span_totals


def read(r):
    return span_totals.ms_each(r, "executor.launch")
