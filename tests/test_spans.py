"""repro.core.spans: totals across threads, nesting, the profiler's host
plane, and the span names a study through a WorkerPool emits."""
import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Bundler, EnsembleExecutor, MerlinRuntime, Step,
                        StudySpec, WorkerPool, spans)
from repro.core.hierarchy import HierarchyCfg

STUDY_SPANS = {"worker.lease", "worker.ack", "worker.wait", "engine.queue",
               "engine.dispatch", "engine.handoff", "engine.write",
               "executor.launch", "executor.host_sync", "bundler.write",
               "runtime.mark"}


def _delta(after, before, name):
    b = before.get(name, {"n": 0, "s": 0.0})
    return after[name]["n"] - b["n"], after[name]["s"] - b["s"]


def test_totals_add_up_across_threads():
    before = spans.totals()
    threads, per_thread = 8, 50

    def work(k):
        for _ in range(per_thread):
            with spans.span("test.threads", task=k):
                pass
            spans.count("test.counted", 0.001, n=2)

    ts = [threading.Thread(target=work, args=(k,)) for k in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in ts)
    after = spans.totals()
    n, s = _delta(after, before, "test.threads")
    assert n == threads * per_thread and s >= 0.0
    n, s = _delta(after, before, "test.counted")
    assert n == 2 * threads * per_thread
    assert abs(s - 0.001 * threads * per_thread) < 1e-9


def test_nested_spans_on_one_thread():
    before = spans.totals()
    with spans.span("test.outer", batch=3) as outer:
        with spans.span("test.inner", task=3) as inner:
            time.sleep(0.01)
        time.sleep(0.005)
    assert outer.t0 <= inner.t0 < inner.t1 <= outer.t1
    after = spans.totals()
    n_in, s_in = _delta(after, before, "test.inner")
    n_out, s_out = _delta(after, before, "test.outer")
    assert n_in == n_out == 1
    assert s_out == outer.t1 - outer.t0 and s_in == inner.t1 - inner.t0
    assert s_out >= s_in + 0.005


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("test.traced", batch=40):
            with spans.span("test.traced_inner", task=50):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    host, = [p for p in data.planes if p.name == "/host:CPU"]
    found = {}
    for line in host.lines:
        for e in line.events:
            name = e.name.split("#", 1)[0]
            if name.startswith("test.traced"):
                found[name] = (e.start_ns, e.duration_ns, dict(e.stats))
    assert set(found) == {"test.traced", "test.traced_inner"}
    o, i = found["test.traced"], found["test.traced_inner"]
    assert o[0] <= i[0] and i[0] + i[1] <= o[0] + o[1]
    assert o[2].get("batch") == 40 and i[2].get("task") == 50


def _sim(u, rng):
    return {"y": jnp.sum(u) + jax.random.uniform(rng),
            "img": jnp.outer(u, u)}


def test_a_study_through_a_worker_pool_emits_every_span(tmp_path):
    rt = MerlinRuntime(workspace=str(tmp_path / "ws"),
                       hierarchy=HierarchyCfg(max_fanout=4, bundle=4))
    bundler = Bundler(str(tmp_path / "results"))
    ex = EnsembleExecutor(_sim, bundler, mesh=None)
    rt.register("simulate", ex.step_fn())
    spec = StudySpec(name="spans", steps=[Step(name="simulate",
                                               fn="simulate")])
    samples = np.random.default_rng(0).random((64, 3), dtype=np.float32)
    before = spans.totals()
    with WorkerPool(rt, n_workers=2) as pool:
        study = rt.run(spec, samples)
        assert rt.wait(study, timeout=120)
    eng = pool.engine.stats()  # after shutdown: the writer has joined
    after = spans.totals()
    assert STUDY_SPANS <= set(after)
    leaves = 64 // 4
    assert _delta(after, before, "bundler.write")[0] == leaves
    assert _delta(after, before, "runtime.mark")[0] == leaves
    assert _delta(after, before, "engine.queue")[0] == leaves
    launches = _delta(after, before, "executor.launch")[0]
    assert _delta(after, before, "executor.host_sync")[0] == launches
    assert _delta(after, before, "engine.write")[0] == \
        _delta(after, before, "engine.dispatch")[0] >= 1
    # the engine's stats carry the same totals, for readers that diff them
    for name in STUDY_SPANS:
        assert before.get(name, {"n": 0})["n"] < eng[f"span_n.{name}"] \
            <= after[name]["n"]
    # the writer is timed once: write_s is the engine.write spans' sum
    wrote = eng["span_s.engine.write"] - before.get(
        "engine.write", {"s": 0.0})["s"]
    assert eng["write_s"] == pytest.approx(wrote, rel=1e-9, abs=1e-9)


def test_the_launch_program_is_named_merlin_ensemble():
    ex = EnsembleExecutor(_sim, None, mesh=None)
    lowered = ex._compiled(8).lower(np.zeros((8, 3), np.float32),
                                    np.arange(8, dtype=np.uint32))
    assert lowered.as_text().startswith("module @jit_merlin_ensemble")
    # the ops' metadata carries the scope, whatever the simulator is
    assert "/merlin_ensemble/" in lowered.compile().as_text()
