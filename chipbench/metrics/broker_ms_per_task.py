"""Runtime/broker: milliseconds of lease and ack (spans ``worker.lease`` and
``worker.ack``) per task the engine executed in the window."""
from chipbench import span_totals


def read(r):
    lease = span_totals.window(r, "worker.lease")
    ack = span_totals.window(r, "worker.ack")
    tasks = (r.get("engine") or {}).get("executed")
    if lease is None or ack is None or not tasks:
        return None
    return 1000.0 * (lease[1] + ack[1]) / tasks
