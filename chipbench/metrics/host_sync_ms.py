"""EnsembleExecutor: milliseconds of one launch's host sync, the wait on the
device and the copy of its outputs to the host (span
``executor.host_sync``)."""
from chipbench import span_totals


def read(r):
    return span_totals.ms_each(r, "executor.host_sync")
