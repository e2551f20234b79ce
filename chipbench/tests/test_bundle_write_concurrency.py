"""``bundle_write_concurrency`` reads a number from the study
readings that carry the group span ``bundler.write_many``, and None from
those of a program without it."""
from chipbench.run import load_module


def _read(engine):
    return load_module("metrics", "bundle_write_concurrency").read(
        {"engine": engine, "window_s": 51.0})


def test_reads_files_per_group_second():
    # 4 files of 60 ms each, written 2.5 at a time over one 96 ms group
    assert _read({"span_n.bundler.write": 4, "span_s.bundler.write": 0.24,
                  "span_n.bundler.write_many": 1,
                  "span_s.bundler.write_many": 0.096}) == 2.5


def test_none_without_the_group_span():
    # the program before groups: per-file spans only
    assert _read({"span_n.bundler.write": 652,
                  "span_s.bundler.write": 44.8}) is None
    assert _read({}) is None
