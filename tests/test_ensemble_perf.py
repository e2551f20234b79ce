"""Hot-path performance machinery: bucketed compile cache, coalesced bundle
execution, scanned surrogate training, incremental archive loads, FileBroker
contention hardening — the regression fences for the fused ensemble path."""
import math
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ensemble as E
from repro.core.active import Surrogate, _mlp_apply, _mlp_init, train_surrogate
from repro.core.bundler import Bundler
from repro.core.hierarchy import HierarchyCfg
from repro.core.queue import FileBroker, new_task
from repro.core.runtime import MerlinRuntime
from repro.core.spec import Step, StudySpec
from repro.core.worker import WorkerPool


def _toy_sim(u, rng):
    """Cheap deterministic-per-seed simulator (fresh fn per test => fresh
    process-wide cache key)."""
    return {"v": u.sum() + jax.random.normal(rng) * 1e-3,
            "inputs": u}


# ---------------------------------------------------------------------------
# bucketed compile cache
# ---------------------------------------------------------------------------

def test_bucketed_compile_count_is_log_bounded():
    def sim(u, rng):
        return {"v": u * 2.0, "s": jax.random.normal(rng)}

    ex = E.EnsembleExecutor(sim)
    rng = np.random.default_rng(0)
    sizes = [1, 2, 3, 4, 5, 6, 7, 9, 11, 13, 16, 21, 27, 5, 3, 27]
    before = E.trace_count()
    lo = 0
    for s in sizes:
        out = ex.run_bundle(lo, lo + s, rng.random((s, 3)).astype(np.float32))
        assert out["v"].shape == (s, 3)  # padding sliced away
        lo += s
    # 13 distinct ragged sizes, but compiles bounded by the bucket schedule
    assert E.trace_count() - before <= math.ceil(math.log2(max(sizes))) + 1
    assert ex.stats["samples"] == sum(sizes)
    assert ex.stats["launches"] == len(sizes)


def test_shared_cache_across_executors():
    def sim(u, rng):
        return {"v": u + 1.0}

    rng = np.random.default_rng(0)
    E.EnsembleExecutor(sim).run_bundle(0, 8, rng.random((8, 2)).astype(np.float32))
    before = E.trace_count()
    # a fresh executor (new bundler, new iteration, new study) reuses the
    # process-wide compiled program: zero new traces
    E.EnsembleExecutor(sim).run_bundle(8, 16, rng.random((8, 2)).astype(np.float32))
    assert E.trace_count() == before


def test_bucketed_results_match_unbucketed(tmp_path):
    rng = np.random.default_rng(3)
    block = rng.random((5, 4)).astype(np.float32)
    b1 = Bundler(str(tmp_path / "a"))
    b2 = Bundler(str(tmp_path / "b"))
    E.EnsembleExecutor(_toy_sim, b1).run_bundle(10, 15, block)
    E.EnsembleExecutor(_toy_sim, b2, bucketed=False,
                       share_cache=False).run_bundle(10, 15, block)
    d1, d2 = b1.load_all(), b2.load_all()
    assert set(d1) == set(d2)
    for k in d1:
        np.testing.assert_allclose(d1[k], d2[k], rtol=1e-6)


# ---------------------------------------------------------------------------
# coalesced execution
# ---------------------------------------------------------------------------

def _run_study(workspace: str, batch: int, samples: np.ndarray):
    rt = MerlinRuntime(workspace=workspace,
                       hierarchy=HierarchyCfg(max_fanout=8, bundle=4))
    bundler = Bundler(os.path.join(workspace, "res"))
    ex = E.EnsembleExecutor(_toy_sim, bundler)
    rt.register("sim", ex.step_fn())
    spec = StudySpec(name="co", steps=[Step(name="sim", fn="sim")])
    with WorkerPool(rt, n_workers=1, batch=batch):
        sid = rt.run(spec, samples)
        assert rt.wait(sid, timeout=120)
    return rt, bundler


def test_coalesced_execution_matches_per_task(tmp_path):
    samples = np.random.default_rng(7).random((24, 4)).astype(np.float32)
    rt1, b1 = _run_study(str(tmp_path / "seq"), 1, samples)     # per-task
    rt2, b2 = _run_study(str(tmp_path / "coal"), 16, samples)   # coalesced
    d1, d2 = b1.load_all(), b2.load_all()
    assert set(d1) == set(d2)
    for k in d1:
        np.testing.assert_allclose(d1[k], d2[k], rtol=1e-6,
                                   err_msg=f"key {k} diverged under coalescing")
    # on-disk layout preserved: one bundle file per original leaf task
    files1 = sorted(f for _, _, fs in os.walk(b1.root) for f in fs)
    files2 = sorted(f for _, _, fs in os.walk(b2.root) for f in fs)
    assert files1 == files2
    # per-sub-bundle idempotency markers all exist in the coalesced run
    study = next(s for s in rt2._specs)
    for lo in range(0, 24, 4):
        assert rt2.counters.once_exists(f"{study}/exec/s0/c0/{lo}_{lo + 4}")


def test_coalesced_poison_task_falls_back_per_task(tmp_path):
    """One failing sub-task must not sink its batch-mates."""
    rt = MerlinRuntime(workspace=str(tmp_path),
                       hierarchy=HierarchyCfg(max_fanout=8, bundle=2))
    done = []

    def step(ctx):
        # poison whenever the (4,6) sub-task is present: fails the fused
        # batch AND every per-task retry of (4,6), so batch-mates can only
        # complete through the runtime's per-task fallback
        if any(tuple(r) == (4, 6) for r in ctx.sub_ranges):
            raise RuntimeError("poison")
        done.append((ctx.lo, ctx.hi))

    rt.register("step", step)
    spec = StudySpec(name="p", steps=[Step(name="step", fn="step")])
    with WorkerPool(rt, n_workers=1, batch=8):
        rt.run(spec, np.zeros((8, 1), np.float32))
        deadline = time.monotonic() + 30
        covered = set()
        while time.monotonic() < deadline:
            covered = set()
            for lo, hi in done:
                covered.update(range(lo, hi))
            if covered >= set(range(8)) - {4, 5}:
                break
            time.sleep(0.05)
    # every non-poison sample executed despite the poison batch-mate
    assert covered >= set(range(8)) - {4, 5}


# ---------------------------------------------------------------------------
# scanned surrogate training
# ---------------------------------------------------------------------------

def _train_reference(X, y, n_members=3, hidden=64, steps=60, lr=3e-3, seed=0):
    """The seed's eager per-member loop (ground truth for parity)."""
    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32)

    def loss_fn(p):
        return jnp.mean((_mlp_apply(p, X) - y) ** 2)

    members = []
    for m in range(n_members):
        rng = jax.random.PRNGKey(seed * 131 + m)
        p = _mlp_init(rng, [X.shape[1], hidden, hidden, 1])
        mom = jax.tree.map(jnp.zeros_like, p)
        vel = jax.tree.map(jnp.zeros_like, p)
        for _ in range(steps):
            g = jax.grad(loss_fn)(p)
            mom = jax.tree.map(lambda m_, g_: 0.9 * m_ + 0.1 * g_, mom, g)
            vel = jax.tree.map(lambda v_, g_: 0.999 * v_ + 0.001 * g_ ** 2,
                               vel, g)
            p = jax.tree.map(
                lambda p_, m_, v_: p_ - lr * m_ / (jnp.sqrt(v_) + 1e-8),
                p, mom, vel)
        members.append(p)
    return Surrogate(members)


def test_scanned_training_matches_eager_loop():
    rng = np.random.default_rng(5)
    X = rng.random((37, 4)).astype(np.float32)  # 37: forces masked padding
    y = (X[:, 0] - 0.3 * X[:, 1] ** 2).astype(np.float32)
    ref = _train_reference(X, y, steps=60)
    new = train_surrogate(X, y, steps=60)
    grid = rng.random((50, 4)).astype(np.float32)
    mu_ref, sd_ref = ref.predict(grid)
    mu_new, sd_new = new.predict(grid)
    np.testing.assert_allclose(mu_new, mu_ref, atol=2e-3)
    np.testing.assert_allclose(sd_new, sd_ref, atol=2e-3)
    # member parameters themselves agree (same init, same update rule)
    for pr, pn in zip(ref.params_list, new.params_list):
        for lr_, ln_ in zip(pr, pn):
            np.testing.assert_allclose(np.asarray(ln_["w"]),
                                       np.asarray(lr_["w"]), atol=2e-3)


def test_train_surrogate_single_compile_across_sizes():
    """Row-bucketing: dataset growth inside one bucket reuses the compile."""
    rng = np.random.default_rng(6)
    X = rng.random((70, 3)).astype(np.float32)
    y = X.sum(1).astype(np.float32)
    s1 = train_surrogate(X[:65], y[:65], steps=30)
    s2 = train_surrogate(X, y, steps=30)  # 65 and 70 both pad to 128
    for s in (s1, s2):
        mu, sd = s.predict(X)
        assert mu.shape == (70,) and sd.shape == (70,)


# ---------------------------------------------------------------------------
# incremental archive loads
# ---------------------------------------------------------------------------

def test_load_all_serves_cache_and_sees_new_bundles(tmp_path):
    b = Bundler(str(tmp_path))
    rng = np.random.default_rng(0)
    b.write_bundle(0, 4, {"y": rng.random(4).astype(np.float32)})
    first = b.load_all()
    again = b.load_all()  # unchanged tree: cached concatenation
    np.testing.assert_array_equal(first["y"], again["y"])
    b.write_bundle(4, 8, {"y": rng.random(4).astype(np.float32)})
    grown = b.load_all()
    assert list(grown["_sample_ids"]) == list(range(8))
    # aggregation rewrites files; the cache must follow, not go stale
    b.aggregate_all()
    agg = b.load_all()
    np.testing.assert_array_equal(agg["y"], grown["y"])


def test_load_since_under_concurrent_writers(tmp_path):
    reader = Bundler(str(tmp_path))
    writer = Bundler(str(tmp_path))
    n_bundles, width = 40, 5

    def write():
        for i in range(n_bundles):
            lo = i * width
            writer.write_bundle(lo, lo + width,
                                {"y": np.full(width, i, np.float32)})
            time.sleep(0.001)

    t = threading.Thread(target=write)
    t.start()
    seen = []
    cursor = None
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        delta, cursor = reader.load_since(cursor)
        if delta:
            seen.extend(int(i) for i in delta["_sample_ids"])
        if len(seen) >= n_bundles * width and not t.is_alive():
            break
        time.sleep(0.002)
    t.join()
    delta, cursor = reader.load_since(cursor)
    if delta:
        seen.extend(int(i) for i in delta["_sample_ids"])
    # every sample delivered exactly once across the cursor chain
    assert sorted(seen) == list(range(n_bundles * width))


# ---------------------------------------------------------------------------
# FileBroker contention (stale-index rename races)
# ---------------------------------------------------------------------------

def test_filebroker_contention_claims_exactly_once(tmp_path):
    root = str(tmp_path / "q")
    producer = FileBroker(root)
    n = 120
    producer.put_many([new_task("real", {"i": i}) for i in range(n)])
    claimed = [[] for _ in range(3)]
    brokers = []

    def drain(k):
        # a long rescan throttle: without forced rescans after stale-claim
        # races, dry spells under contention would starve this consumer
        b = FileBroker(root, rescan_interval=5.0)
        brokers.append(b)
        while True:
            lease = b.get(timeout=0.5)
            if lease is None:
                return
            claimed[k].append(lease.task.payload["i"])
            b.ack(lease.tag)

    threads = [threading.Thread(target=drain, args=(k,)) for k in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    got = sorted(i for part in claimed for i in part)
    assert got == list(range(n))  # nothing lost, nothing double-claimed
    # separate instances on one directory: rename races must have occurred
    assert sum(b.stats["stale_claims"] for b in brokers) > 0
    assert producer.idle()


# ---------------------------------------------------------------------------
# the bench itself cannot rot
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_ensemble_bench_smoke(tmp_path):
    from benchmarks import ensemble_throughput as ET
    from benchmarks.bench_schema import ENSEMBLE_SPEC, check_doc
    out = str(tmp_path / "BENCH_ensemble.json")
    r = ET.run(quick=True, out=out, workroot=str(tmp_path),
               n_tasks=6, max_bundle=8, sur_rows=32, sur_steps=25,
               load_bundles=5, xb_samples=48, xb_bundle=4,
               mesh_tasks=2, mesh_bundle=16)
    import json
    with open(out) as f:
        on_disk = json.load(f)
    assert on_disk["meta"]["bench"] == "ensemble_throughput"
    # the artifact the bench writes satisfies its documented schema
    assert check_doc(on_disk, ENSEMBLE_SPEC, "smoke") == []
    for scen in ("ragged", "uniform"):
        row = r[scen]
        assert row["baseline"]["samples"] == row["fused"]["samples"]
        assert row["speedup"] > 0
        assert row["fused"]["traces"] <= row["bucket_bound"]
    xb = r["engine_xbatch"]
    assert xb["per_worker"]["samples_per_s"] > 0
    assert xb["xbatch"]["samples_per_s"] > 0
    assert xb["xbatch"]["engine"]["batches"] >= 1
    md = r["mesh_dispatch"]  # a failed child raises inside run()
    assert md["bit_equal"] is True
    assert md["jag_max_rel_diff"] <= 1e-3
    assert r["surrogate"]["prediction_max_abs_diff"] < 1e-2
    assert r["loads"]["warm_load_s"] <= r["loads"]["cold_load_s"]
