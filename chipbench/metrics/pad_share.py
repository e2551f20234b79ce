"""EnsembleExecutor: padded rows as a share of all rows launched in the
window, in %."""


def read(r):
    x = r.get("executor")
    if not x:
        return None
    rows = x["samples"] + x["padded_samples"]
    return 100.0 * x["padded_samples"] / rows if rows else None
