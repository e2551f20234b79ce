"""95th percentile, over every request due in the window, of the time
from its due time to its 200 reply.  A request without a 200 ranks above
every latency; where the percentile falls on one, the value is the span
of the whole run (first due time to last reply), which exceeds them all."""


def read(r):
    if r.get("kind") != "serve_open_loop":
        return None
    lat = sorted(r["latencies_ms"])
    rank = max(0, -(-95 * len(lat) // 100) - 1)  # nearest rank
    v = lat[rank]
    return v if v != float("inf") else r["span_ms"]
