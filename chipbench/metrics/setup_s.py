"""Seconds from process start to the window's opening: imports, device
start, inputs from the seed, compiles (from the persistent cache when
warm) and warm-up."""


def read(r):
    return r["setup_s"]
