"""EnsembleExecutor: simulator traces (compiles) inside the window; every
shape is warmed in set-up, so this should read 0."""


def read(r):
    return r.get("window_traces")
