"""ExecutionEngine: milliseconds a task waited in the engine's buffer, from
submit to being taken into a batch (counter ``engine.queue``)."""
from chipbench import span_totals


def read(r):
    return span_totals.ms_each(r, "engine.queue")
