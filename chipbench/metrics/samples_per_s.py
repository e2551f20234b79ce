"""Samples simulated and durably bundled per second: samples of leaf
tasks acked inside the window (each ack follows its bundle's rename into
place), over the window's seconds."""


def read(r):
    if r.get("kind") != "study_backlog":
        return None
    return r["samples_acked"] / r["window_s"]
