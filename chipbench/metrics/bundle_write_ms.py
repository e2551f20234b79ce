"""Bundler: milliseconds to compress and publish one bundle file,
``np.savez_compressed`` and the rename (span ``bundler.write``)."""
from chipbench import span_totals


def read(r):
    return span_totals.ms_each(r, "bundler.write")
