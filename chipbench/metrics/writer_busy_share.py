"""Engine writer + Bundler: share of the window the writer thread spent in
host sync, npz compression and rename (change of ``write_s``), in %."""


def read(r):
    e = r.get("engine")
    if not e or "write_s" not in e:
        return None
    return 100.0 * e["write_s"] / r["window_s"]
