"""ContinuousBatcher: requests per launch over the window (change of
``completed`` + ``failed`` over change of ``batches``)."""


def read(r):
    b = r.get("batcher")
    if not b or not b.get("batches"):
        return None
    return (b["completed"] + b["failed"]) / b["batches"]
