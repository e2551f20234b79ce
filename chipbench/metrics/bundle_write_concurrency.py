"""Engine writer + Bundler: bundle files compressed at once, on average,
while a fused launch's files are written: seconds of ``bundler.write``
(one span a file, on whichever thread wrote it) over seconds of
``bundler.write_many`` (one span a launch's group, on the engine's writer
thread) in the window.  About 1 where a group is written file after file;
None for a program that has no group span."""
from chipbench import span_totals


def read(r):
    files = span_totals.window(r, "bundler.write")
    groups = span_totals.window(r, "bundler.write_many")
    if not files or not groups or not groups[1]:
        return None
    return files[1] / groups[1]
