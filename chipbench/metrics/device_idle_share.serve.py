"""Device, serving cells: 1 - busy / window over the traced slice, from the
profiler trace (busy: union of device-op intervals, mean over chips), in %."""


def read(r):
    t = r.get("trace")
    if r.get("kind") != "serve_open_loop" or not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
