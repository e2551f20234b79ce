"""Bundling/aggregation + crawl invariants (paper Fig. 7)."""
import os
import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import bundler as bundler_mod
from repro.core.bundler import POOL_MIN_FILE_BYTES, Bundler, missing_samples


def test_write_aggregate_load_roundtrip(tmp_path):
    b = Bundler(str(tmp_path), files_per_leaf=3)
    rng = np.random.default_rng(0)
    truth = rng.random((30, 4)).astype(np.float32)
    for lo in range(0, 30, 5):
        b.write_bundle(lo, lo + 5, {"x": truth[lo:lo + 5]})
    present, corrupt = b.crawl()
    assert present == set(range(30)) and not corrupt
    b.aggregate_all()
    # bundles subsumed: only aggregates remain
    files = [f for _, _, fs in os.walk(str(tmp_path)) for f in fs]
    assert all(f == "aggregate.npz" for f in files)
    data = b.load_all()
    assert np.allclose(data["x"], truth)


def test_crawl_detects_corruption(tmp_path):
    b = Bundler(str(tmp_path))
    b.write_bundle(0, 5, {"x": np.ones(5)})
    b.write_bundle(5, 10, {"x": np.ones(5)})
    # corrupt one file in place
    victim = None
    for root, _, files in os.walk(str(tmp_path)):
        for f in files:
            if f.startswith("bundle_000000005"):
                victim = os.path.join(root, f)
    assert victim is not None
    with open(victim, "wb") as f:
        f.write(b"garbage")
    present, corrupt = b.crawl()
    assert present == set(range(5))
    assert len(corrupt) == 1


@given(st.sets(st.integers(0, 199)))
@settings(max_examples=50, deadline=None)
def test_missing_samples_ranges(present):
    ranges = missing_samples(200, present)
    rebuilt = set()
    for lo, hi in ranges:
        assert lo < hi
        rebuilt.update(range(lo, hi))
    assert rebuilt == set(range(200)) - present
    # ranges are maximal (no two adjacent)
    for (a, b), (c, d) in zip(ranges, ranges[1:]):
        assert c > b


def test_concurrent_writers_no_interference(tmp_path):
    import threading
    b = Bundler(str(tmp_path), files_per_leaf=10)

    def write(lo):
        Bundler(str(tmp_path), files_per_leaf=10).write_bundle(
            lo, lo + 2, {"x": np.full(2, lo)})

    ts = [threading.Thread(target=write, args=(lo,)) for lo in range(0, 40, 2)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    present, corrupt = b.crawl()
    assert present == set(range(40)) and not corrupt


# ---------------------------------------------------------------------------
# write_bundles: the files of one fused launch as one group
# ---------------------------------------------------------------------------

POOL_PREFIX = "merlin-bundle-writer-"


def _group(n_files=4, rows=10, lo0=0, row_bytes=None):
    """(lo, hi, results) per file; by default each file's arrays hold just
    over POOL_MIN_FILE_BYTES, so the group goes to the writer pool."""
    rng = np.random.default_rng(lo0)
    row_bytes = row_bytes or POOL_MIN_FILE_BYTES // rows + 64
    items = []
    for i in range(n_files):
        lo = lo0 + i * rows
        items.append((lo, lo + rows, {
            "x": rng.random((rows, row_bytes // 4)).astype(np.float32),
            "y": rng.random(rows).astype(np.float32)}))
    return items


def _tree(root):
    return sorted(os.path.relpath(os.path.join(d, f), root)
                  for d, _, fs in os.walk(root) for f in fs)


class _Probe(Bundler):
    """Records which thread wrote each file and when it was renamed; can
    slow or fail chosen files (keyed by ``lo``)."""

    def __init__(self, root, slow=None, fail=(), **kw):
        super().__init__(root, **kw)
        self.slow = dict(slow or {})
        self.fail = set(fail)
        self.threads = {}
        self.renamed = {}

    def write_bundle(self, lo, hi, results):
        self.threads[lo] = threading.current_thread().name
        time.sleep(self.slow.get(lo, 0.0))
        if lo in self.fail:
            raise OSError(f"disk full at {lo}")
        path = super().write_bundle(lo, hi, results)
        self.renamed[lo] = time.perf_counter()
        return path


def test_write_bundles_matches_serial_writes(tmp_path):
    items = _group()
    serial = Bundler(str(tmp_path / "serial"))
    for it in items:
        serial.write_bundle(*it)
    probe = _Probe(str(tmp_path / "group"))
    paths = probe.write_bundles(items)
    # the group went to the pool, one file a thread call
    assert all(probe.threads[lo].startswith(POOL_PREFIX) for lo, _, _ in items)
    # same names (no tmp file left behind), same arrays
    assert _tree(probe.root) == _tree(serial.root)
    assert [os.path.relpath(p, probe.root) for p in paths] == _tree(serial.root)
    for rel in _tree(serial.root):
        with np.load(os.path.join(serial.root, rel)) as a, \
                np.load(os.path.join(probe.root, rel)) as b:
            assert a.files == b.files
            for k in a.files:
                np.testing.assert_array_equal(a[k], b[k])
    present, corrupt = probe.crawl()
    assert present == set(range(40)) and not corrupt
    ids = np.concatenate([np.load(p)["_sample_ids"] for p in paths])
    assert sorted(ids.tolist()) == list(range(40))  # each id exactly once


def test_write_bundles_failure_waits_for_the_group(tmp_path):
    items = _group()
    # 30 fails at once and 0 a little later; 10 and 20 are still being
    # written when both have failed
    probe = _Probe(str(tmp_path), slow={0: 0.05, 10: 0.3, 20: 0.3},
                   fail={0, 30})
    with pytest.raises(OSError, match="disk full at 0"):  # first in order
        probe.write_bundles(items)
    raised = time.perf_counter()
    assert set(probe.renamed) == {10, 20}
    assert max(probe.renamed.values()) <= raised
    # a per-file rerun of the group, as the engine's fallback does
    probe.fail.clear()
    probe.slow.clear()
    for it in items:
        probe.write_bundle(*it)
    present, corrupt = probe.crawl()
    assert present == set(range(40)) and not corrupt
    assert not [f for f in _tree(probe.root) if ".tmp-" in f]


@pytest.mark.parametrize("items", [
    # small files, as an SEIR study writes
    [(lo, lo + 10, {"x": np.ones((10, 64), np.float32)}) for lo in range(0, 40, 10)],
    _group(n_files=1),  # a single-task launch
    _group(n_files=3) + _group(n_files=1, lo0=30, row_bytes=4096),  # one small
], ids=["small_files", "one_file", "one_small_file"])
def test_write_bundles_under_threshold_stays_inline(tmp_path, monkeypatch, items):
    monkeypatch.setattr(bundler_mod, "_POOL", None)
    probe = _Probe(str(tmp_path))
    probe.write_bundles(items)
    me = threading.current_thread().name
    assert [probe.threads[lo] for lo, _, _ in items] == [me] * len(items)
    assert bundler_mod._POOL is None  # no pool thread was started
    present, _ = probe.crawl()
    assert present == {i for lo, hi, _ in items for i in range(lo, hi)}


def test_write_bundles_feeds_sink_in_order_after_renames(tmp_path):
    items = _group()

    class Sink:
        def __init__(self):
            self.seen = []

        def push_bundle(self, lo, hi, results):
            on_disk = probe.crawl()[0] == set(range(40))
            self.seen.append((lo, hi, threading.current_thread().name,
                              on_disk, results is items_by_lo[lo]))

    items_by_lo = {lo: r for lo, _, r in items}
    # the first file lands last: a push as each file lands would be out of
    # order and early
    probe = _Probe(str(tmp_path), slow={0: 0.2}, sink=Sink())
    probe.write_bundles(items)
    me = threading.current_thread().name
    assert probe.threads[0].startswith(POOL_PREFIX)
    assert probe.sink.seen == [(lo, hi, me, True, True) for lo, hi, _ in items]
