"""ExecutionEngine: leaf tasks per fused batch over the window
(change of ``executed`` over change of ``batches``)."""


def read(r):
    e = r.get("engine")
    if not e or not e.get("batches"):
        return None
    return e["executed"] / e["batches"]
