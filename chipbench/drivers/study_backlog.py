"""Driver ``study_backlog``: a study backlog through the real entry points.

Set-up draws the configuration's whole backlog of inputs from the seed,
compiles every launch shape the cell can use (one to ``workers`` fused
bundles), runs a small warm-up study through the same path, and starts a
``WorkerPool`` on a fresh ``MerlinRuntime``.  The window opens with
``MerlinRuntime.run`` (the enqueue) and lasts ``--seconds``, or until the
last leaf task of the backlog is acked.

``samples_per_s`` counts the samples of leaf tasks acked inside the
window.  A worker acks a leaf task only after its bundle file was renamed
into place, so an acked sample is a durably bundled one.  The acks are
stamped by a thin wrapper around the broker (``AckClock``).

After the window the pool is shut down and the check reads the bundle
files back from disk: every acked sample id must be present exactly once,
and a sample of rows drawn from the seed (every failed shot of the chosen
files among them) must match the plain reference.
"""
from __future__ import annotations

import os
import sys
import threading
import time

import numpy as np


class AckClock:
    """A broker that stamps every ack of a leaf task with the host clock.

    Delegates everything to the broker it wraps; ``acks`` holds
    ``(t, lo, hi)`` for each acked leaf task."""

    def __init__(self, inner):
        self._inner = inner
        self._lock = threading.Lock()
        self._leased = {}
        self.acks = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def get_many(self, n, timeout=0.0, queues=None):
        leases = self._inner.get_many(n, timeout=timeout, queues=queues)
        with self._lock:
            for lease in leases:
                if lease.task.kind == "real":
                    self._leased[lease.tag] = tuple(lease.task.payload["samples"])
        return leases

    def get(self, timeout=0.0, queues=None):
        leases = self.get_many(1, timeout=timeout, queues=queues)
        return leases[0] if leases else None

    def ack(self, tag):
        self.ack_many([tag])

    def ack_many(self, tags):
        tags = list(tags)
        self._inner.ack_many(tags)
        now = time.perf_counter()
        with self._lock:
            for tag in tags:
                span = self._leased.pop(tag, None)
                if span is not None:
                    self.acks.append((now, span[0], span[1]))

    def acked_samples(self) -> int:
        with self._lock:
            return sum(hi - lo for _, lo, hi in self.acks)


def _study(workspace, sim, samples, cell, mesh, broker=None):
    """A runtime, bundler, executor and spec wired as a user wires them."""
    from repro.core import (Bundler, EnsembleExecutor, MerlinRuntime, Step,
                            StudySpec)
    from repro.core.hierarchy import HierarchyCfg
    rt = MerlinRuntime(broker=broker, workspace=workspace,
                       hierarchy=HierarchyCfg(max_fanout=cell["fanout"],
                                              bundle=cell["bundle"]))
    bundler = Bundler(os.path.join(workspace, "results"),
                      files_per_leaf=cell["files_per_leaf"])
    ex = EnsembleExecutor(sim, bundler, mesh=mesh)
    rt.register("simulate", ex.step_fn())
    spec = StudySpec(name="chipbench", steps=[Step(name="simulate",
                                                   fn="simulate")])
    return rt, bundler, ex, spec


def _warm_up(run, sim, inputs, mesh) -> None:
    """Compile every fused launch shape, then drive a small study through
    the same entry points so that first-use costs fall in set-up."""
    from repro.core import EnsembleExecutor, WorkerPool
    cell = run.cell
    bundle, workers = cell["bundle"], cell["workers"]
    shapes = EnsembleExecutor(sim, None, mesh=mesh)
    for k in range(1, workers + 1):
        shapes.run_bundle(0, k * bundle, inputs[:k * bundle])
    n = 2 * workers * bundle
    rt, _, _, spec = _study(os.path.join(run.workdir, "warm"), sim,
                            inputs[:n], cell, mesh)
    with WorkerPool(rt, n_workers=workers) as pool:
        study = rt.run(spec, inputs[:n])
        if not rt.wait(study, timeout=300):
            raise RuntimeError(f"warm-up study did not finish: {pool.stats()}")


def _bundle_files(root: str):
    """(path, sample ids) of every published bundle file under ``root``."""
    out = []
    for dirpath, _, files in os.walk(root):
        for f in sorted(files):
            if f.endswith(".npz") and not f.startswith("."):
                path = os.path.join(dirpath, f)
                with np.load(path) as z:
                    out.append((path, np.asarray(z["_sample_ids"])))
    return out


def _diff(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float))
            and isinstance(before.get(k), (int, float))}


def run(run):
    from repro.core import WorkerPool
    from repro.core import ensemble as E
    from repro.core.queue import InMemoryBroker

    cell, cfg = run.cell, run.config
    sim = run.simulator()
    n = int(cfg["n_samples"])
    inputs = np.random.default_rng(run.seed).random(
        (n, int(cfg["input_dims"])), dtype=np.float32)
    mesh = "auto" if int(cell["chips"]) > 1 else None
    _warm_up(run, sim, inputs, mesh)

    broker = AckClock(InMemoryBroker())
    rt, bundler, ex, spec = _study(os.path.join(run.workdir, "study"), sim,
                                   inputs, cell, mesh, broker=broker)
    pool = WorkerPool(rt, n_workers=int(cell["workers"]))
    try:
        eng0, ex0, tr0 = pool.engine.stats(), dict(ex.stats), E.trace_count()
        t_open = run.window_opens()
        rt.run(spec, inputs)
        t_end = t_open + run.seconds
        if run.trace:
            time.sleep(max(0.0, t_open + cell["trace_at"] * run.seconds
                           - time.perf_counter()))
            run.trace_start()
            time.sleep(max(0.0, min(cell["trace_s"],
                                    t_end - time.perf_counter())))
            run.trace_stop()
        while time.perf_counter() < t_end and broker.acked_samples() < n:
            time.sleep(0.02)
        t_close = min(time.perf_counter(), t_end)
        eng1, ex1, tr1 = pool.engine.stats(), dict(ex.stats), E.trace_count()
    finally:
        pool.shutdown()
    pool_stats = pool.stats()
    if broker.acked_samples() >= n:  # drained early: close at the last ack
        t_close = max(t for t, _, _ in broker.acks)
    window_s = t_close - t_open
    # how the acks spread over the window: a ramp or a stall shows here
    quarters = [0] * 4
    for t, lo, hi in broker.acks:
        if t_open <= t <= t_close:
            quarters[min(3, int(4 * (t - t_open) / window_s))] += hi - lo
    first = min((t for t, _, _ in broker.acks), default=t_open) - t_open
    print(f"acks: first after {first!r} s; samples by quarter of the window "
          f"{quarters}", file=sys.stderr, flush=True)
    run.read_memory_peak()

    in_window = {(lo, hi) for t, lo, hi in broker.acks if t <= t_close}
    acked = {(lo, hi) for _, lo, hi in broker.acks}
    files = _bundle_files(bundler.root)
    count = np.bincount(np.concatenate([ids for _, ids in files]),
                        minlength=n) if files else np.zeros(n, int)
    missing = sum(int((count[lo:hi] == 0).sum()) for lo, hi in acked)
    duplicate = int((count > 1).sum())
    window_files = [(p, ids) for p, ids in files
                    if (int(ids[0]), int(ids[-1]) + 1) in in_window]

    checks = _check_values(run, window_files, inputs)
    failed = sum(pool_stats[k] for k in ("failed", "dead_lettered", "skipped"))
    checks.update(missing_ids={"value": missing, "limit": 0},
                  duplicate_ids={"value": duplicate, "limit": 0},
                  failed_tasks={"value": failed, "limit": 0})
    return {
        "kind": "study_backlog",
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(acked) + pool_stats["failed"],
        "failed": failed,
        "checks": checks,
        "window_s": window_s,
        "samples_acked": sum(hi - lo for lo, hi in in_window),
        "engine": _diff(eng1, eng0),
        "executor": _diff(ex1, ex0),
        "window_traces": tr1 - tr0,
        "bundle_bytes": sum(os.path.getsize(p) for p, _ in window_files),
        "bundle_samples": sum(len(ids) for _, ids in window_files),
    }


def _check_values(run, window_files, inputs) -> dict:
    """Rows read back from bundle files published in the window, against
    the reference: ``check_files`` files drawn from the seed, and in each
    its failed shots plus rows drawn from the seed, ``check_ids_per_file``
    in all."""
    cell, cfg, ref = run.cell, run.config, run.reference
    rng = np.random.default_rng(run.seed + 1)
    want = int(cell["check_files"])
    picks = rng.permutation(len(window_files))[:want]
    failed_key = cfg.get("failed_key")
    got, ids = {}, []
    for i in sorted(picks):
        path, file_ids = window_files[i]
        with np.load(path) as z:
            data = {k: np.asarray(z[k]) for k in cfg["outputs"]}
        ok = ~ref.ambiguous(inputs[file_ids])
        rows = np.flatnonzero(ok)
        first = np.array([], int)
        if failed_key:
            first = np.flatnonzero(ok & (data[failed_key] > 0.5))[:8]
        rest = rng.permutation(np.setdiff1d(rows, first))
        rows = np.sort(np.concatenate(
            [first, rest[:int(cell["check_ids_per_file"]) - len(first)]]))
        ids.append(file_ids[rows])
        for k, v in data.items():
            got.setdefault(k, []).append(v[rows])
    checks = {"unchecked_files": {"value": want - len(picks), "limit": 0}}
    if not ids:
        return checks
    ids = np.concatenate(ids)
    got = {k: np.concatenate(v) for k, v in got.items()}
    res = ref.gap(got, ref.simulate(ids, inputs[ids]))
    for k, v in res.items():
        checks[k] = {"value": v, "limit": ref.LIMITS[k]}
    return checks
