"""ExecutionEngine: cross-worker micro-batching, drain/shutdown flush
semantics, poison isolation inside fused batches, engine lifecycle, and
the multi-device shard_map dispatch equivalence (subprocess, forced
8-device host)."""
import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import ensemble as E
from repro.core.bundler import Bundler
from repro.core.engine import (ContinuousBatcher, DeadlineExpired,
                               EngineClosed, ExecutionEngine, PendingTask,
                               task_lo)
from repro.core.hierarchy import HierarchyCfg
from repro.core.queue import PRIORITY_REAL, BrokerFull, new_task
from repro.core.resilience import RetryPolicy
from repro.core.runtime import MerlinRuntime
from repro.core.spec import Step, StudySpec
from repro.core.worker import WorkerPool


def _seed_study(rt: MerlinRuntime, study: str, spans, n_samples: int,
                bundle: int, fn: str = "sim") -> None:
    """Register a study and enqueue its leaf tasks directly (the resubmit
    path): the node counter expects exactly len(spans) bundles."""
    spec = StudySpec(name=study, steps=[Step(name=fn, fn=fn)])
    samples = np.random.default_rng(0).random(
        (n_samples, 3)).astype(np.float32)
    rt.register_study(spec, study_id=study, samples=samples)
    rt.broker.put_many([
        new_task("real", {"study": study, "stage": 0, "combo": 0,
                          "n_samples": n_samples, "bundle": bundle,
                          "fanout": 16, "samples": [lo, hi],
                          "real_queue": "real", "gen_queue": "gen"},
                 priority=PRIORITY_REAL, queue="real")
        for lo, hi in spans])


# ---------------------------------------------------------------------------
# adaptive deadline (EMA of submission inter-arrival gaps)
# ---------------------------------------------------------------------------

class _StubRuntime:
    """Execution sink for engine-only unit tests."""

    def execute_real_many(self, tasks):
        pass

    def execute_real(self, task):
        pass


def test_adaptive_flush_cuts_lone_straggler_latency():
    """When arrivals are slower than the batching window, waiting out the
    full deadline cannot buy fusion — the engine flushes after the idle
    grace (max_wait / 4) instead."""
    eng = ExecutionEngine(_StubRuntime(), max_batch=64, max_wait_ms=400.0,
                          adaptive=True)
    try:
        # first submission: no EMA yet -> full deadline applies
        p0 = eng.submit(new_task("real", {"i": 0}))
        time.sleep(0.8)  # a slow feed: gap (0.8s) >> max_wait (0.4s)
        assert p0.done()  # flushed by its deadline long ago
        t0 = time.monotonic()
        p1 = eng.submit(new_task("real", {"i": 1}))
        assert p1.wait(5.0)
        waited = time.monotonic() - t0
        # idle grace is 100ms; the full window would be 400ms
        assert waited < 0.35, f"adaptive flush too slow: {waited:.3f}s"
        s = eng.stats()
        assert s["adaptive_flushes"] >= 1
        assert s["ema_gap_ms"] > 400.0
    finally:
        eng.close()


def test_adaptive_engine_leaves_bursts_alone():
    """Back-to-back submissions (gap << max_wait) must batch exactly as
    before: no adaptive flush fires, the size rule still wins."""
    eng = ExecutionEngine(_StubRuntime(), max_batch=8, max_wait_ms=300.0,
                          adaptive=True)
    try:
        pendings = eng.submit_many([new_task("real", {"i": i})
                                    for i in range(8)])
        assert all(p.wait(5.0) for p in pendings)
        s = eng.stats()
        assert s["size_flushes"] == 1
        assert s["adaptive_flushes"] == 0
        assert s["max_batch_seen"] == 8
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# cross-worker coalescing
# ---------------------------------------------------------------------------

def test_cross_worker_fusion_exceeds_per_worker_batch(tmp_path):
    """4 workers at batch 4 feeding one engine: at least one fused context
    must span MORE leaf tasks than any single worker's lease batch — the
    cross-get_many / cross-worker coalescing the per-worker path cannot
    do."""
    rt = MerlinRuntime(workspace=str(tmp_path))
    calls = []
    rt.register("sim", lambda ctx: calls.append(list(map(tuple,
                                                         ctx.sub_ranges))))
    spans = [(i * 2, (i + 1) * 2) for i in range(16)]
    _seed_study(rt, "xw", spans, n_samples=32, bundle=2)
    # max_wait well above scheduler jitter so the fused batch forms from
    # a size or drain flush, not a deadline flush racing worker leases
    with WorkerPool(rt, n_workers=4, batch=4,
                    engine_cfg={"max_batch": 16, "max_wait_ms": 2000}) as p:
        assert p.drain(timeout=60)
        eng_stats = p.stats()["engine"]
    covered = sorted(r for call in calls for r in call)
    assert covered == spans  # every leaf executed exactly once
    assert max(len(c) for c in calls) > 4  # fused beyond one lease batch
    assert eng_stats["max_batch_seen"] > 4
    assert eng_stats["batches"] >= 1
    # histogram and flush-reason accounting are coherent
    assert sum(eng_stats["batch_hist"].values()) == eng_stats["batches"]
    assert (eng_stats["size_flushes"] + eng_stats["deadline_flushes"]
            + eng_stats["forced_flushes"]) == eng_stats["batches"]
    assert eng_stats["executed"] == 16


def test_engine_coalesces_across_queues(tmp_path):
    """Tasks leased from different QUEUES but the same study/stage/combo
    land in one buffer and fuse (compatibility is execution identity, not
    queue identity)."""
    rt = MerlinRuntime(workspace=str(tmp_path))
    calls = []
    rt.register("sim", lambda ctx: calls.append(len(ctx.sub_ranges)))
    spec = StudySpec(name="q2", steps=[Step(name="sim", fn="sim")])
    rt.register_study(spec, study_id="q2",
                      samples=np.zeros((8, 2), np.float32))
    tasks = []
    for i in range(4):  # alternate contiguous spans across two queues
        tasks.append(new_task(
            "real", {"study": "q2", "stage": 0, "combo": 0, "n_samples": 8,
                     "bundle": 2, "fanout": 16, "samples": [i * 2, i * 2 + 2],
                     "real_queue": "real", "gen_queue": "gen"},
            priority=PRIORITY_REAL, queue="sims-a" if i % 2 else "sims-b"))
    rt.broker.put_many(tasks)
    # max_wait well above scheduler jitter: the flush under test is the
    # one drain() forces after every task is leased, not a deadline flush
    # racing the second worker's lease (deadline flushes have their own
    # tests below)
    with WorkerPool(rt, n_workers=2, batch=2, queues=("sims-a", "sims-b"),
                    engine_cfg={"max_batch": 8, "max_wait_ms": 2000}) as p:
        assert p.drain(timeout=60)
    assert sum(calls) == 4
    assert max(calls) > 2  # spans from both queues fused into one launch


# ---------------------------------------------------------------------------
# drain / shutdown flush semantics
# ---------------------------------------------------------------------------

def test_drain_flushes_partial_microbatch(tmp_path):
    """A partially-filled buffer under a HUGE max_wait must not strand
    leased tasks: drain() forces the flush."""
    rt = MerlinRuntime(workspace=str(tmp_path))
    done = []
    rt.register("sim", lambda ctx: done.append((ctx.lo, ctx.hi)))
    _seed_study(rt, "dr", [(0, 2), (2, 4), (4, 6)], 6, 2)
    t0 = time.monotonic()
    with WorkerPool(rt, n_workers=1, batch=4,
                    engine_cfg={"max_batch": 64,
                                "max_wait_ms": 60_000}) as p:
        assert p.drain(timeout=30)
        elapsed = time.monotonic() - t0
    assert sorted(r for c in done for r in [c]) and len(done) >= 1
    assert sum(hi - lo for lo, hi in done) == 6
    assert elapsed < 20  # nowhere near the 60s batching deadline
    assert rt.broker.idle()  # all acked, nothing left to expire


def test_shutdown_flushes_partial_microbatch(tmp_path):
    """shutdown() without a prior drain must also execute + ack the
    buffered partial batch (not abandon the leases)."""
    rt = MerlinRuntime(workspace=str(tmp_path))
    done = []
    rt.register("sim", lambda ctx: done.extend(map(tuple, ctx.sub_ranges)))
    _seed_study(rt, "sd", [(0, 2), (2, 4)], 4, 2)
    pool = WorkerPool(rt, n_workers=1, batch=2,
                      engine_cfg={"max_batch": 64, "max_wait_ms": 60_000})
    # wait until both tasks are leased and submitted (buffer holds them)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and rt.broker.qsize() > 0:
        time.sleep(0.01)
    pool.shutdown()
    assert sorted(done) == [(0, 2), (2, 4)]
    assert rt.broker.idle()  # acked on the way out, not left to expire


# ---------------------------------------------------------------------------
# poison isolation in fused cross-worker batches
# ---------------------------------------------------------------------------

def test_poison_in_fused_batch_dead_letters_alone(tmp_path):
    """One poison task inside a cross-worker fused batch must dead-letter
    by itself (retries exhausted -> acked away) while every sibling
    executes and acks."""
    rt = MerlinRuntime(workspace=str(tmp_path))
    done = []

    def step(ctx):
        if any(tuple(r) == (4, 6) for r in ctx.sub_ranges):
            raise RuntimeError("poison")
        done.extend(map(tuple, ctx.sub_ranges))

    rt.register("sim", step)
    spans = [(i * 2, (i + 1) * 2) for i in range(8)]
    _seed_study(rt, "px", spans, 16, 2)
    with WorkerPool(rt, n_workers=2, batch=4,
                    retry_policy=RetryPolicy(max_retries=2),
                    engine_cfg={"max_batch": 8, "max_wait_ms": 50}) as p:
        assert p.drain(timeout=60)  # reaches idle => poison dead-lettered
        stats = p.stats()
    assert sorted(set(done)) == [s for s in spans if s != (4, 6)]
    assert (4, 6) not in done
    assert stats["failed"] >= 1  # the poison task's failures were recorded
    assert rt.broker.idle()
    # siblings completed exactly once each (once-markers all present)
    for lo, hi in spans:
        marked = rt.counters.once_exists(f"px/exec/s0/c0/{lo}_{hi}")
        assert marked == ((lo, hi) != (4, 6))


def test_cmd_and_funnel_tasks_bypass_engine(tmp_path):
    """Only parallel fn-step stages are engine-fusable; cmd-step and
    funnel tasks run in the worker's own thread (N workers = N concurrent
    subprocesses), so a slow cmd step cannot head-of-line-block the
    dispatcher."""
    from repro.core.spec import Step, StudySpec
    rt = MerlinRuntime(workspace=str(tmp_path))
    spec = StudySpec(name="mix", steps=[
        Step(name="sim", cmd="true"),
        Step(name="post", fn="post", depends=("sim_*",),
             over_samples=False)])
    rt.register_study(spec, study_id="mix")
    cmd_task = new_task("real", {"study": "mix", "stage": 0, "combo": 0,
                                 "n_samples": 4, "bundle": 2, "fanout": 4,
                                 "samples": [0, 2]})
    funnel_task = new_task("real", {"study": "mix", "stage": 1, "combo": 0,
                                    "n_samples": 4, "bundle": 2,
                                    "fanout": 4, "samples": [0, 1]})
    unknown = new_task("real", {"study": "nope", "stage": 0, "combo": 0,
                                "samples": [0, 1]})
    assert not rt.coalescable(cmd_task)
    assert not rt.coalescable(funnel_task)
    assert not rt.coalescable(unknown)
    rt2 = MerlinRuntime(workspace=str(tmp_path / "w2"))
    rt2.register("sim", lambda ctx: None)
    spec2 = StudySpec(name="fn", steps=[Step(name="sim", fn="sim")])
    rt2.register_study(spec2, study_id="fn")
    fn_task = new_task("real", {"study": "fn", "stage": 0, "combo": 0,
                                "n_samples": 4, "bundle": 2, "fanout": 4,
                                "samples": [0, 2]})
    assert rt2.coalescable(fn_task)


def test_base_exception_in_step_never_acks_unexecuted_siblings(tmp_path):
    """A step raising a BaseException (SystemExit) must not let the
    dispatcher resolve batch-mates as successes they never earned: every
    task either executed (resolved None) or comes back as a failure for
    redelivery — at-least-once survives."""
    rt = MerlinRuntime(workspace=str(tmp_path))
    done = []

    def step(ctx):
        if any(tuple(r) == (2, 4) for r in ctx.sub_ranges):
            raise SystemExit(1)  # not an Exception subclass
        done.extend(map(tuple, ctx.sub_ranges))

    rt.register("sim", step)
    spans = [(0, 2), (2, 4), (4, 6), (6, 8)]
    _seed_study(rt, "be", spans, 8, 2)
    leases = rt.broker.get_many(4, timeout=1)
    eng = ExecutionEngine(rt, max_batch=4, max_wait_ms=5)
    pendings = eng.submit_many([l.task for l in leases])
    for p in pendings:
        assert p.wait(30)
    by_span = {tuple(p.task.payload["samples"]): p for p in pendings}
    assert isinstance(by_span[(2, 4)].error, SystemExit)
    for span in ((0, 2), (4, 6), (6, 8)):
        assert by_span[span].error is None  # executed via fallback
        assert span in done
    assert (2, 4) not in done
    eng.close()
    for lease in leases:
        rt.broker.ack(lease.tag)


# ---------------------------------------------------------------------------
# lifecycle: shared engine, refcounts, closed-engine behavior
# ---------------------------------------------------------------------------

def test_shared_engine_refcount_across_pools(tmp_path):
    rt = MerlinRuntime(workspace=str(tmp_path))
    rt.register("sim", lambda ctx: None)
    p1 = WorkerPool(rt, n_workers=1)
    p2 = WorkerPool(rt, n_workers=1)
    assert p1.engine is p2.engine  # one scheduler per runtime
    p1.shutdown()
    assert not p1.engine.closed  # p2 still attached
    p2.shutdown()
    assert p2.engine.closed  # last pool out closes the dispatcher
    p3 = WorkerPool(rt, n_workers=1)  # a fresh engine is created
    assert p3.engine is not p1.engine and not p3.engine.closed
    p3.shutdown()


def test_submit_to_closed_engine_raises(tmp_path):
    rt = MerlinRuntime(workspace=str(tmp_path))
    eng = ExecutionEngine(rt)
    eng.close()
    with pytest.raises(EngineClosed):
        eng.submit(new_task("real", {}))


def test_close_resolves_buffered_handles(tmp_path):
    """close() executes the buffered batch (forced flush), so handles
    resolve instead of hanging their waiters."""
    rt = MerlinRuntime(workspace=str(tmp_path))
    done = []
    rt.register("sim", lambda ctx: done.append((ctx.lo, ctx.hi)))
    _seed_study(rt, "cl", [(0, 3)], 3, 3)
    lease = rt.broker.get(timeout=1)
    eng = ExecutionEngine(rt, max_batch=64, max_wait_ms=60_000)
    pending = eng.submit(lease.task)
    eng.close()
    assert pending.done()
    assert pending.error is None and done == [(0, 3)]
    rt.broker.ack(lease.tag)


# ---------------------------------------------------------------------------
# multi-device shard_map dispatch (forced 8-device subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_mesh_dispatch_matches_single_device_bit_for_bit():
    """The acceptance equivalence: shard_map dispatch over 8 forced host
    devices is bit-for-bit identical to single-device execution for an
    IEEE-exact simulator (and within last-ULP transcendental codegen
    variance for the JAG stand-in), with compiles inside the bucketed
    bound.  Runs in a subprocess because the in-process suite pins the
    1-device default (tests/conftest.py)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    cfg = {"sizes": [32, 32, 16, 5]}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.ensemble_throughput",
         "--mesh-worker", json.dumps(cfg)],
        capture_output=True, text=True, env=env, cwd=root, timeout=590)
    assert proc.returncode == 0, proc.stderr[-1000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 8
    assert out["bit_equal"] is True
    assert out["jag_max_rel_diff"] <= 1e-3
    # compile count: one trace per bucket per path, within the bound
    for tag in ("exact_sharded", "jag_sharded", "exact_single",
                "jag_single"):
        assert out[tag]["traces"] <= out["bucket_bound"]
    # the sharded streams actually used the mesh (32- and 16-buckets
    # divide 8 devices; the 5->8 bucket does too)
    assert out["exact_sharded"]["mesh_launches"] >= 3
    assert out["exact_single"].get("mesh_launches", 0) == 0


# ---------------------------------------------------------------------------
# executor mesh plumbing that does not need a subprocess
# ---------------------------------------------------------------------------

def test_single_device_auto_mesh_is_none():
    """On the suite's 1-device host, mesh='auto' degrades to exactly the
    old single-device behavior."""
    ex = E.EnsembleExecutor(lambda u, rng: {"v": u}, mesh="auto")
    assert ex.mesh is None
    assert ex.stats["devices"] == 1
    out = ex.run_bundle(0, 3, np.zeros((3, 2), np.float32))
    assert out["v"].shape == (3, 2)
    assert ex.stats["mesh_launches"] == 0


# ---------------------------------------------------------------------------
# affinity-keyed batching (per-study engine affinity)
# ---------------------------------------------------------------------------

class _AffinityStub:
    """Records which affinity keys each fused launch mixed."""

    def __init__(self):
        self.batches = []

    def affinity_key(self, task):
        return task.payload["study"]

    def execute_real_many(self, tasks):
        self.batches.append([t.payload["study"] for t in tasks])

    def execute_real(self, task):
        self.batches.append([task.payload["study"]])


def test_affinity_key_keeps_interleaved_studies_apart():
    """Two studies submitting interleaved through one shared engine: no
    fused launch may mix studies (each study's ensemble executor has its
    own jit cache and bundle archive), and the short dispatch is counted
    as an affinity split."""
    rt = _AffinityStub()
    eng = ExecutionEngine(rt, max_batch=8, max_wait_ms=60.0,
                          adaptive=False)
    try:
        tasks = [new_task("real", {"study": "a" if i % 2 == 0 else "b",
                                   "i": i}) for i in range(8)]
        pendings = eng.submit_many(tasks)
        assert all(p.wait(10.0) for p in pendings)
        s = eng.stats()
    finally:
        eng.close()
    assert len(rt.batches) >= 2  # one fused launch would have mixed keys
    for batch in rt.batches:
        assert len(set(batch)) == 1, f"launch mixed studies: {batch}"
    assert sorted(k for b in rt.batches for k in b) == ["a"] * 4 + ["b"] * 4
    # the front group dispatched short (4 < max_batch) with "b" waiting
    assert s["affinity_splits"] >= 1


# ---------------------------------------------------------------------------
# deferred host writes (single writer thread overlapping dispatch)
# ---------------------------------------------------------------------------

class _DeferStub:
    """Runtime exposing the deferred-write pipeline with visible phases."""

    def __init__(self, compute_s=0.0, write_s=0.0):
        self.compute_s = compute_s
        self.write_s = write_s
        self.events = []

    def execute_real_many_deferred(self, tasks):
        time.sleep(self.compute_s)
        self.events.append(("compute", len(tasks)))

        def finalize():
            time.sleep(self.write_s)
            self.events.append(("finalize", len(tasks)))
        return finalize

    def execute_real_many(self, tasks):
        pass

    def execute_real(self, task):
        pass


def test_deferred_pipeline_resolves_only_after_finalize():
    """Ack-after-durable: a handle may not resolve until the writer ran
    the batch's finalize (host sync + bundle write + once-markers)."""
    rt = _DeferStub(write_s=0.05)
    eng = ExecutionEngine(rt, max_batch=4, max_wait_ms=20.0)
    try:
        pendings = eng.submit_many([new_task("real", {"i": i})
                                    for i in range(4)])
        assert all(p.wait(10.0) for p in pendings)
        # resolution implies the writer already finalized this batch
        assert ("finalize", 4) in rt.events
        s = eng.stats()
        assert s["deferred_batches"] == 1
        assert s["write_s"] > 0.0
        assert "write_overlap_s" in s
    finally:
        eng.close()


def test_deferred_writes_overlap_next_dispatch():
    """Back-to-back batches: batch N's finalize runs on the writer thread
    while the dispatcher is already computing batch N+1, and the overlap
    shows up in stats["write_overlap_s"]."""
    rt = _DeferStub(compute_s=0.05, write_s=0.05)
    eng = ExecutionEngine(rt, max_batch=1, max_wait_ms=5.0)
    try:
        pendings = eng.submit_many([new_task("real", {"i": i})
                                    for i in range(3)])
        assert all(p.wait(20.0) for p in pendings)
        s = eng.stats()
        assert s["deferred_batches"] == 3
        assert s["write_overlap_s"] > 0.0, \
            "finalize never overlapped a dispatch"
    finally:
        eng.close()


class _SlowBundler(Bundler):
    """Slowed file writes, slowest on the writer pool; records each group's
    files, which files a pool thread wrote, and any two writes of one file
    (one tmp name) at once.  With ``fail_first_group`` the first file of
    the first multi-file group fails fast, once."""

    def __init__(self, root, fail_first_group=False):
        super().__init__(root)
        self.lock = threading.Lock()
        self.groups = []
        self.in_flight = set()
        self.collisions = []
        self.pool_files = 0
        self.fail_first_group = fail_first_group
        self.failed = None

    def write_bundles(self, items):
        with self.lock:
            self.groups.append([(lo, hi) for lo, hi, _ in items])
            if self.fail_first_group and len(items) > 1:
                self.fail_first_group = False
                self.failed = items[0][:2]
        return super().write_bundles(items)

    def write_bundle(self, lo, hi, results):
        pooled = threading.current_thread().name.startswith(
            "merlin-bundle-writer-")
        with self.lock:
            if (lo, hi) in self.in_flight:
                self.collisions.append((lo, hi))
            self.in_flight.add((lo, hi))
            fail = (lo, hi) == self.failed and pooled
            self.pool_files += pooled
        try:
            if fail:
                raise OSError(f"disk full at {lo}")
            time.sleep(0.3 if pooled else 0.02)
            return super().write_bundle(lo, hi, results)
        finally:
            with self.lock:
                self.in_flight.discard((lo, hi))

    def path(self, lo, hi):
        return os.path.join(self.leaf_dir(lo, hi - lo),
                            f"bundle_{lo:09d}_{hi:09d}.npz")


def _wide_sim(u, rng):
    """32 KB a sample: a 10-sample bundle file passes the pool's threshold."""
    return {"v": jax.random.normal(rng, (8192,)) + u.sum(), "inputs": u}


@pytest.mark.parametrize("fail_first_group", [False, True],
                         ids=["pooled", "finalize_fails"])
def test_deferred_pooled_writes_ack_after_durable(tmp_path, monkeypatch,
                                                   fail_first_group):
    """Through the engine's deferred path with the writer pool on: no
    handle of a fused batch resolves before every file of that batch is
    on disk.  A group whose file fails falls back per task, and the rerun
    never writes a file (its tmp name) while a pool thread still is."""
    b = _SlowBundler(str(tmp_path / "res"), fail_first_group)
    early = []
    resolve = PendingTask._resolve

    def checked_resolve(self, error):
        lo = task_lo(self.task)
        if error is None and lo >= 0:
            with b.lock:
                groups = [g for g in b.groups if any(r[0] == lo for r in g)]
            early.extend(r for g in groups for r in g
                         if not os.path.exists(b.path(*r)))
        resolve(self, error)

    monkeypatch.setattr(PendingTask, "_resolve", checked_resolve)
    rt = MerlinRuntime(workspace=str(tmp_path),
                       hierarchy=HierarchyCfg(max_fanout=16, bundle=10))
    rt.register("sim", E.EnsembleExecutor(_wide_sim, b).step_fn())
    spec = StudySpec(name="wide", steps=[Step(name="sim", fn="sim")])
    samples = np.random.default_rng(3).random((80, 3)).astype(np.float32)
    # compile the one-task program first, so a per-task rerun writes while
    # a pool thread of the failed group would still be writing
    E.EnsembleExecutor(_wide_sim, None).run_bundle(0, 10, samples[:10])
    with WorkerPool(rt, n_workers=1, batch=8) as pool:
        sid = rt.run(spec, samples)
        assert rt.wait(sid, timeout=120) and pool.drain(timeout=60)
        eng = pool.stats()["engine"]
    assert eng["deferred_batches"] >= 1 and eng["failed_tasks"] == 0
    assert max(len(g) for g in b.groups) > 1 and b.pool_files > 1
    assert early == [] and b.collisions == []
    if fail_first_group:  # the fused group failed, then each task ran alone
        tries = [g for g in b.groups if b.failed in g]
        assert len(tries[0]) > 1 and tries[-1] == [b.failed]
    present, corrupt = b.crawl()
    assert present == set(range(80)) and not corrupt
    names = [f for _, _, fs in os.walk(b.root) for f in fs]
    assert len(names) == 8 and not [f for f in names if ".tmp-" in f]


# ---------------------------------------------------------------------------
# serving: ContinuousBatcher (admission, deadlines, shed, drain)
# ---------------------------------------------------------------------------

class _Gate:
    """infer_fn whose FIRST call blocks on an event — lets a test park
    the batcher loop mid-launch while follow-up requests queue up."""

    def __init__(self):
        self.event = threading.Event()
        self.calls = []  # first-column values of each launch, in order

    def __call__(self, X):
        first = not self.calls
        self.calls.append(np.array(X[:, 0]))
        if first:
            assert self.event.wait(10.0)
        return X * 2.0

    def wait_entered(self):
        for _ in range(1000):
            if self.calls:
                return
            time.sleep(0.005)
        raise AssertionError("batcher loop never entered infer_fn")


def test_batcher_fuses_requests_queued_behind_a_launch():
    """Requests arriving while a batch executes are admitted together
    into the next launch, and each caller gets exactly its own slice."""
    gate = _Gate()
    b = ContinuousBatcher(gate, max_batch_rows=64, max_inflight=32)
    try:
        hold = b.submit(np.zeros((2, 3), np.float32))
        gate.wait_entered()
        reqs = [b.submit(np.full((2, 3), float(i), np.float32))
                for i in range(4)]
        gate.event.set()
        assert hold.wait(10.0) and all(r.wait(10.0) for r in reqs)
        assert np.allclose(hold.result, 0.0)
        for i, r in enumerate(reqs):  # per-request slices, not batch-mates'
            assert r.result.shape == (2, 3)
            assert np.allclose(r.result, 2.0 * i)
        s = b.stats()
        assert s["batches"] == 2  # 1 held launch + 1 fused launch of 4
        assert s["batch_requests_hist"].get(4) == 1
        assert s["completed"] == 5 and s["failed"] == 0
    finally:
        b.close()


def test_batcher_naive_mode_is_flush_per_request():
    """The A/B baseline: naive mode launches exactly one request per
    batch even when the queue is deep."""
    calls = []

    def infer(X):
        calls.append(len(X))
        time.sleep(0.02)  # slow enough that peers pile up behind it
        return X

    b = ContinuousBatcher(infer, naive=True, max_inflight=32)
    try:
        reqs = [b.submit(np.ones((2, 2), np.float32)) for _ in range(5)]
        assert all(r.wait(10.0) for r in reqs)
        s = b.stats()
        assert s["batches"] == 5
        assert set(s["batch_requests_hist"]) == {1}
    finally:
        b.close()


def test_batcher_admission_is_deadline_ordered():
    """Under backlog the deadline-carrying request is admitted ahead of
    an earlier-submitted request with no deadline."""
    gate = _Gate()
    b = ContinuousBatcher(gate, max_inflight=16)
    try:
        hold = b.submit(np.zeros((1, 2), np.float32))
        gate.wait_entered()
        slack = b.submit(np.full((1, 2), 1.0, np.float32))  # no deadline
        urgent = b.submit(np.full((1, 2), 2.0, np.float32),
                          deadline_s=30.0)
        gate.event.set()
        assert all(r.wait(10.0) for r in (hold, slack, urgent))
    finally:
        b.close()
    fused = np.concatenate(gate.calls[1:])
    assert fused[0] == 2.0, f"deadline request not first: {fused}"


def test_batcher_bucket_boundary_topup():
    """Admission grows the batch to max_batch_rows, then keeps topping up
    only while rows still fit the power-of-two bucket the batch already
    pays padding for: queued 5+3+2 rows at max_batch_rows=8 must launch
    as [8, 2], never [10]."""
    gate = _Gate()
    b = ContinuousBatcher(gate, max_batch_rows=8, max_inflight=16)
    try:
        hold = b.submit(np.zeros((1, 2), np.float32))
        gate.wait_entered()
        reqs = [b.submit(np.ones((n, 2), np.float32)) for n in (5, 3, 2)]
        gate.event.set()
        assert hold.wait(10.0) and all(r.wait(10.0) for r in reqs)
    finally:
        b.close()
    assert [len(c) for c in gate.calls] == [1, 8, 2]


def test_batcher_deadline_expires_without_executing():
    """A request whose deadline passes while queued resolves with
    DeadlineExpired and its rows never reach infer_fn (504 semantics)."""
    gate = _Gate()
    b = ContinuousBatcher(gate, max_inflight=16)
    try:
        hold = b.submit(np.zeros((1, 2), np.float32))
        gate.wait_entered()
        doomed = b.submit(np.full((1, 2), 7.0, np.float32),
                          deadline_s=0.05)
        time.sleep(0.15)  # deadline passes while the loop is parked
        gate.event.set()
        assert hold.wait(10.0) and doomed.wait(10.0)
        assert isinstance(doomed.error, DeadlineExpired)
        assert doomed.result is None
        s = b.stats()
        assert s["expired"] == 1
        # accounting identity: every admitted request is accounted for
        assert s["completed"] + s["failed"] + s["expired"] == s["submitted"]
    finally:
        b.close()
    assert all(7.0 not in c for c in gate.calls), "expired request executed"


def test_batcher_sheds_with_brokerfull_before_admission():
    """At max_inflight queued requests, submit raises BrokerFull (429
    semantics) without admitting — and the queued requests still finish."""
    gate = _Gate()
    b = ContinuousBatcher(gate, max_inflight=2)
    try:
        hold = b.submit(np.zeros((1, 2), np.float32))
        gate.wait_entered()  # hold left the heap; queue is empty again
        queued = [b.submit(np.ones((1, 2), np.float32)) for _ in range(2)]
        with pytest.raises(BrokerFull):
            b.submit(np.ones((1, 2), np.float32))
        assert b.stats()["shed"] == 1
        gate.event.set()
        assert hold.wait(10.0) and all(r.wait(10.0) for r in queued)
        assert all(r.error is None for r in queued)  # shed cost no one else
    finally:
        b.close()


def test_batcher_drain_completes_admitted_then_refuses():
    """drain(): already-admitted requests run to completion while new
    submissions are refused with EngineClosed (the gateway's 503)."""
    gate = _Gate()
    b = ContinuousBatcher(gate, max_inflight=16)
    hold = b.submit(np.zeros((1, 2), np.float32))
    gate.wait_entered()
    queued = [b.submit(np.ones((1, 2), np.float32)) for _ in range(3)]
    drained = []
    t = threading.Thread(target=lambda: drained.append(b.drain(10.0)))
    t.start()
    for _ in range(1000):  # wait for drain() to flip the admission gate
        try:
            b.submit(np.ones((1, 2), np.float32))
        except EngineClosed:
            break
        time.sleep(0.005)
    else:
        raise AssertionError("drain never started refusing admissions")
    gate.event.set()
    t.join(timeout=15.0)
    assert drained == [True]
    assert hold.wait(1.0) and all(r.wait(1.0) for r in queued)
    assert all(r.error is None for r in queued)
    b.close()


def test_batcher_close_resolves_backlog_with_engineclosed():
    """close() without drain must never strand a waiter: anything still
    queued resolves with EngineClosed."""
    gate = _Gate()
    b = ContinuousBatcher(gate, max_inflight=16)
    hold = b.submit(np.zeros((1, 2), np.float32))
    gate.wait_entered()
    queued = b.submit(np.ones((1, 2), np.float32))
    gate.event.set()
    b.close()
    assert hold.wait(10.0) and queued.wait(10.0)
    # the held request was mid-execution (completes); anything the loop
    # did not reach before close resolves, with a typed error if dropped
    assert queued.done()
    assert queued.error is None or isinstance(queued.error, EngineClosed)
