"""Ensemble hot-path throughput benchmark (the post-broker bottleneck).

Five measurements, each comparing the fused hot path against the seed
("baseline") behavior re-created faithfully inside this process:

* **ragged** — the optimization-loop scenario: a stream of ragged-size
  bundles (the sizes an active-learning loop actually produces).  Baseline
  constructs a fresh ``EnsembleExecutor`` per task with a private,
  exact-size jit cache (the seed's ``OptimizationLoop._sim_step``); fused
  uses one process-wide executor with power-of-two bucket padding.
* **uniform** — the same comparison on fixed-size bundles, isolating the
  executor-construction / cache-reuse win from the bucketing win.
* **surrogate** — deep-ensemble training wall-clock: the seed's eager
  per-member Python loop (jit re-closed per member => recompile per member,
  ``steps`` dispatches each) vs the single jitted ``lax.scan`` over steps
  vmapped over members.
* **engine_xbatch** — cross-worker coalescing: the same ragged leaf-task
  stream drained by 4 lease-pump workers at batch 4, once with per-worker
  coalescing only (``engine=None``, the pre-engine path: each worker can
  fuse at most its OWN 4-lease window, and the four threads execute
  concurrently — convoying on the GIL for the host-side work each launch
  drags along: padding, device transfer, result conversion, bundle
  writes) and once through the shared micro-batching ExecutionEngine
  (tasks from all four workers accumulate into one buffer and flush as
  4x-wider fused launches in ONE executing thread, with the workers
  reduced to cheap event waiters).  Acceptance: >= 2x samples/s.
* **mesh_dispatch** — multi-device shard_map dispatch, run in a
  subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
  (the in-process bench keeps the 1-device default): fused bundles on one
  device vs shard_mapped over the 8-device mesh, with the equivalence
  fields the acceptance test relies on (strict bit-for-bit for an
  IEEE-exact simulator; <= 1e-3 max relative diff for the
  transcendental-heavy JAG stand-in: vectorized pow/exp codegen may
  legally differ in the last ULP across per-shard batch widths, and the
  ~v^5.8 power laws amplify that into ~1e-4 relative) and the
  compile-count bound.  On a CPU host the 8 "devices" share the same
  cores, so throughput parity — not speedup — is expected; the scenario
  exists to prove correctness + compile accounting of the dispatch path
  that pays off on real multi-device hosts.

Recompile counts come from ``repro.core.ensemble.trace_count()`` (a counter
incremented inside the traced function, i.e. once per XLA compile).

Writes ``BENCH_ensemble.json`` at the repo root — schema documented in
benchmarks/README.md.

Usage: PYTHONPATH=src python -m benchmarks.ensemble_throughput [--quick]
       [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import time
from typing import Dict, List

import numpy as np

DEFAULT_OUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "BENCH_ensemble.json"))


# ---------------------------------------------------------------------------
# ragged / uniform bundle execution
# ---------------------------------------------------------------------------

def ragged_sizes(n_tasks: int, max_bundle: int, seed: int = 0) -> List[int]:
    """A plausible optimization-loop size stream: mostly full bundles with a
    ragged tail per iteration, plus odd resubmission fragments."""
    rng = np.random.default_rng(seed)
    sizes = []
    while len(sizes) < n_tasks:
        full, tail = divmod(int(rng.integers(1, 4) * max_bundle + rng.integers(0, max_bundle)),
                            max_bundle)
        sizes.extend([max_bundle] * full)
        if tail:
            sizes.append(tail)
        if rng.random() < 0.3:  # a crawl-and-resubmit fragment
            sizes.append(int(rng.integers(1, max(2, max_bundle // 2))))
    return sizes[:n_tasks]


def _run_stream(sizes: List[int], fused: bool, workdir: str) -> Dict:
    """Execute one bundle per size; returns wall-clock + trace accounting."""
    import jax  # noqa: F401  (imported late so --help stays fast)
    from repro.core import ensemble as E
    from repro.core.bundler import Bundler
    from repro.sim import jag_simulate

    # a per-stream wrapper gives each scenario its own compile-cache key,
    # so every measurement pays its own compiles (no cross-scenario warmth)
    def simulator(u, rng):
        return jag_simulate(u, rng)

    rng = np.random.default_rng(1)
    blocks = [rng.random((s, 5)).astype(np.float32) for s in sizes]
    bundler = Bundler(workdir)
    t_traces = E.trace_count()
    shared = E.EnsembleExecutor(simulator, bundler) if fused else None
    lo = 0
    t0 = time.perf_counter()
    for block in blocks:
        hi = lo + len(block)
        if fused:
            ex = shared
        else:
            # the seed hot path: fresh executor per task, private cache,
            # exact-size compile (bucketing off)
            ex = E.EnsembleExecutor(simulator, bundler, bucketed=False,
                                    share_cache=False)
        ex.run_bundle(lo, hi, block)
        lo = hi
    wall = time.perf_counter() - t0
    n = sum(sizes)
    return {"tasks": len(sizes), "samples": n, "wall_s": wall,
            "samples_per_s": n / wall,
            "traces": E.trace_count() - t_traces}


def bench_bundles(n_tasks: int, max_bundle: int, workroot: str) -> Dict:
    import tempfile
    out: Dict = {}
    for name, sizes in (
            ("ragged", ragged_sizes(n_tasks, max_bundle)),
            ("uniform", [max_bundle] * n_tasks)):
        row: Dict = {"max_bundle": max_bundle}
        for mode in ("baseline", "fused"):
            with tempfile.TemporaryDirectory(dir=workroot) as d:
                row[mode] = _run_stream(sizes, mode == "fused", d)
        row["speedup"] = (row["fused"]["samples_per_s"]
                         / row["baseline"]["samples_per_s"])
        # the bucket schedule bounds fused compiles: one per power-of-two
        # bucket <= max bundle size in the stream
        row["bucket_bound"] = int(math.ceil(math.log2(max(sizes)))) + 1
        out[name] = row
    return out


# ---------------------------------------------------------------------------
# cross-worker micro-batching (ExecutionEngine)
# ---------------------------------------------------------------------------

def ragged_partition(n: int, k: int, seed: int = 0):
    """Partition [0, n) into exactly k contiguous ragged spans — the shape
    of a crawl-and-resubmit stream (the stage counter expects k bundles)."""
    rng = np.random.default_rng(seed)
    cuts = np.sort(rng.choice(np.arange(1, n), size=k - 1, replace=False))
    bounds = [0, *cuts.tolist(), n]
    return list(zip(bounds[:-1], bounds[1:]))


def _xbatch_run(simulator, spans, n_samples: int, bundle: int,
                workroot: str, use_engine: bool, workers: int = 4,
                batch: int = 4) -> Dict:
    """Drain one ragged leaf-task stream with a 4-worker pool; returns
    wall-clock + launch accounting.  Leaf tasks are enqueued directly
    (the resubmit path) so both modes see the IDENTICAL task stream."""
    import tempfile
    from repro.core import ensemble as E
    from repro.core.bundler import Bundler
    from repro.core.queue import PRIORITY_REAL, new_task
    from repro.core.runtime import MerlinRuntime
    from repro.core.spec import Step, StudySpec
    from repro.core.worker import WorkerPool

    with tempfile.TemporaryDirectory(dir=workroot) as ws:
        rt = MerlinRuntime(workspace=ws)
        bundler = Bundler(os.path.join(ws, "res"))
        ex = E.EnsembleExecutor(simulator, bundler)
        rt.register("sim", ex.step_fn())
        spec = StudySpec(name="xb", steps=[Step(name="sim", fn="sim")])
        study = "xb-bench"
        rng = np.random.default_rng(7)
        rt.register_study(spec, study_id=study,
                          samples=rng.random((n_samples, 5))
                          .astype(np.float32))
        tasks = [new_task("real",
                          {"study": study, "stage": 0, "combo": 0,
                           "n_samples": n_samples, "bundle": bundle,
                           "fanout": 16, "samples": [lo, hi],
                           "real_queue": "real", "gen_queue": "gen"},
                          priority=PRIORITY_REAL, queue="real")
                 for lo, hi in spans]
        rt.broker.put_many(tasks)
        engine_cfg = {"max_batch": workers * batch, "max_wait_ms": 25.0}
        t0 = time.perf_counter()
        with WorkerPool(rt, n_workers=workers, batch=batch,
                        engine="auto" if use_engine else None,
                        engine_cfg=engine_cfg) as pool:
            done = pool.drain(timeout=600)
            wall = time.perf_counter() - t0
            stats = pool.stats()
        assert done, "xbatch scenario failed to drain"
        out = {"wall_s": wall, "samples_per_s": n_samples / wall,
               "launches": ex.stats["launches"],
               "device_util": ex.stats["samples"] /
               max(ex.stats["samples"] + ex.stats["padded_samples"], 1)}
        if "engine" in stats:
            eng = stats["engine"]
            out["engine"] = {k: eng[k] for k in
                            ("batches", "avg_batch", "max_batch_seen",
                             "size_flushes", "deadline_flushes",
                             "forced_flushes", "utilization")}
        return out


def bench_engine_xbatch(n_samples: int, bundle: int, workroot: str,
                        repeats: int = 3) -> Dict:
    """Per-worker coalescing vs the shared engine on one ragged stream.

    Best of ``repeats`` interleaved runs per mode, after an untimed
    warmup run of each (first-run effects — thread-pool spin-up, cold
    page cache on the workspace tmpfs, CPU governor ramp — hit whichever
    mode goes first by ~2x on small hosts)."""
    from repro.core import ensemble as E
    from repro.sim import jag_simulate

    def simulator(u, rng):  # scenario-private compile-cache key
        return jag_simulate(u, rng)

    k = n_samples // bundle
    spans = ragged_partition(n_samples, k)
    # warm every bucket a fused run could hit (both modes share the cache,
    # so neither timed run pays compiles — we measure dispatch, not XLA)
    warm = E.EnsembleExecutor(simulator)
    rng = np.random.default_rng(3)
    for b in E.bucket_schedule(E.bucket_for(n_samples)):
        warm.run_bundle(0, b, rng.random((b, 5)).astype(np.float32))
    warm_spans = ragged_partition(n_samples // 4, max(2, k // 4))
    modes: Dict[str, Dict] = {}
    for r in range(-1, repeats):  # interleaved: box-load drift hits both
        for name, use_engine in (("per_worker", False), ("xbatch", True)):
            if r < 0:  # warmup lap: run small, discard
                _xbatch_run(simulator, warm_spans, n_samples // 4, bundle,
                            workroot, use_engine)
                continue
            res = _xbatch_run(simulator, spans, n_samples, bundle,
                              workroot, use_engine)
            best = modes.get(name)
            if best is None or res["samples_per_s"] > best["samples_per_s"]:
                modes[name] = res
    return {"n_samples": n_samples, "tasks": k, "bundle": bundle,
            "workers": 4, "batch": 4, **modes,
            "speedup": (modes["xbatch"]["samples_per_s"]
                        / modes["per_worker"]["samples_per_s"])}


# ---------------------------------------------------------------------------
# multi-device shard_map dispatch (subprocess: forces 8 host devices)
# ---------------------------------------------------------------------------

def _exact_sim_src():
    """An IEEE-exact simulator (add/mul/div/sqrt + counter-based uniform
    bits only): every op is correctly rounded per element, so any batch
    split produces bit-identical results — the strict half of the
    equivalence check."""
    import jax
    import jax.numpy as jnp

    def exact_sim(u, rng):
        s = u * 2.0 + 0.25
        noise = jax.random.uniform(rng, u.shape) * 0.001
        return {"v": s / (1.0 + u) + noise,
                "w": jnp.sqrt(s),
                "s": (u * u).sum()}
    return exact_sim


def mesh_worker_main(cfg: Dict) -> None:
    """Entrypoint for the forced-8-device subprocess (``--mesh-worker``)."""
    import jax
    from repro.core import ensemble as E
    from repro.sim import jag_simulate

    def jag(u, rng):
        return jag_simulate(u, rng)

    exact = _exact_sim_src()
    sizes = cfg["sizes"]
    blocks = [np.random.default_rng(5).random((s, 5)).astype(np.float32)
              for s in sizes]
    out: Dict = {"devices": jax.local_device_count(), "sizes": sizes,
                 "bucket_bound": int(math.ceil(
                     math.log2(max(sizes)))) + 1}

    def stream(ex, tag):
        t_traces = E.trace_count()
        results = []
        lo = 0
        t0 = time.perf_counter()
        for blk in blocks:
            results.append(ex.run_bundle(lo, lo + len(blk), blk))
            lo += len(blk)
        wall = time.perf_counter() - t0
        n = sum(sizes)
        out[tag] = {"wall_s": wall, "samples_per_s": n / wall,
                    "traces": E.trace_count() - t_traces,
                    "mesh_launches": ex.stats["mesh_launches"]}
        return results

    # strict bit-for-bit: IEEE-exact simulator
    r1 = stream(E.EnsembleExecutor(exact, mesh=None), "exact_single")
    r2 = stream(E.EnsembleExecutor(exact), "exact_sharded")
    out["bit_equal"] = all(
        np.array_equal(np.asarray(a[k]), np.asarray(b[k]), equal_nan=True)
        for a, b in zip(r1, r2) for k in a)
    # transcendental-heavy JAG: last-ULP codegen variance allowed
    j1 = stream(E.EnsembleExecutor(jag, mesh=None), "jag_single")
    j2 = stream(E.EnsembleExecutor(jag), "jag_sharded")
    rel = 0.0
    for a, b in zip(j1, j2):
        for k in a:
            x, y = np.asarray(a[k]), np.asarray(b[k])
            m = np.isfinite(x)
            d = np.abs(x - y)[m] / np.maximum(np.abs(x[m]), 1e-30)
            if d.size:
                rel = max(rel, float(d.max()))
    out["jag_max_rel_diff"] = rel
    print(json.dumps(out), flush=True)


def bench_mesh_dispatch(n_tasks: int, bundle: int,
                        devices: int = 8) -> Dict:
    """Run the mesh scenario in a subprocess with forced host devices.

    The child is a CPU equivalence check by design: it is pinned to the
    CPU backend (a parent that already holds an accelerator would make a
    device-hungry child fail or hang), and its failure raises rather than
    dropping the scenario.  The chip version is ``chip_smoke.py
    --four-chips``."""
    import subprocess
    import sys

    import repro.core  # repro itself may be a namespace package (no file)
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(repro.core.__file__))))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # uniform bundles (shardable buckets) plus one ragged tail exercising
    # the small-bucket single-device fallback
    cfg = {"sizes": [bundle] * n_tasks + [max(2, bundle // 5)]}
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={devices}"
                        ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [src, root] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                       if p])
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.ensemble_throughput",
         "--mesh-worker", json.dumps(cfg)],
        capture_output=True, text=True, env=env, cwd=root, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"mesh worker failed (rc {proc.returncode}): "
                           f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# surrogate training
# ---------------------------------------------------------------------------

def _train_reference(X, y, n_members=3, hidden=64, steps=300, lr=3e-3, seed=0):
    """The seed's eager per-member loop, verbatim (kept here as the
    baseline; core/active.py now trains with one scanned compile)."""
    import jax
    import jax.numpy as jnp
    from repro.core.active import Surrogate, _mlp_apply, _mlp_init

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

        @jax.jit
        def step(p, mom, vel, i):
            g = jax.grad(loss_fn)(p)
            mom = jax.tree.map(lambda m_, g_: 0.9 * m_ + 0.1 * g_, mom, g)
            vel = jax.tree.map(lambda v_, g_: 0.999 * v_ + 0.001 * g_ ** 2,
                               vel, g)
            p = jax.tree.map(
                lambda p_, m_, v_: p_ - lr * m_ / (jnp.sqrt(v_) + 1e-8),
                p, mom, vel)
            return p, mom, vel

        for i in range(steps):
            p, mom, vel = step(p, mom, vel, i)
        members.append(p)
    return Surrogate(members)


def bench_surrogate(n_rows: int, steps: int, repeats: int = 3) -> Dict:
    """Per-call training wall-clock at the optimization loop's archive size.

    The loop trains two surrogates per iteration, every iteration, on an
    archive of batch_per_iter × iters rows (~50–200).  The seed loop
    re-closes and re-jits its step per member on EVERY call, so each call
    pays n_members compiles plus steps × members eager dispatches — that
    recurring cost is the baseline (min over calls; every call recompiles
    by construction).  The scanned trainer compiles once per row-bucket per
    process (reported as ``scanned_cold_s``) and every subsequent call runs
    warm (``scanned_s`` = min over warm calls) — the steady-state cost the
    loop actually pays from its second training call onward."""
    from repro.core.active import train_surrogate

    rng = np.random.default_rng(0)
    X = rng.random((n_rows, 5)).astype(np.float32)
    y = (np.sin(3 * X[:, 0]) + X[:, 1] ** 2).astype(np.float32)
    y = (y - y.min()) / (y.max() - y.min())

    def timed(fn, seed):
        t0 = time.perf_counter()
        sur = fn(seed)
        sur.predict(X[:8])  # force any pending device work
        return time.perf_counter() - t0, sur

    base_calls, scan_calls = [], []
    sur_b = sur_s = None
    for r in range(repeats):
        dt, sur_b = timed(lambda s: _train_reference(X, y, steps=steps,
                                                     seed=s), 0)
        base_calls.append(dt)
        dt, sur_s = timed(lambda s: train_surrogate(X, y, steps=steps,
                                                    seed=s), 0)
        scan_calls.append(dt)
    mu_b, _ = sur_b.predict(X)
    mu_s, _ = sur_s.predict(X)
    base_s = min(base_calls)
    scan_s = min(scan_calls[1:]) if len(scan_calls) > 1 else scan_calls[0]
    return {"rows": n_rows, "steps": steps,
            "baseline_s": base_s, "scanned_s": scan_s,
            "scanned_cold_s": scan_calls[0],
            "speedup": base_s / scan_s,
            "prediction_max_abs_diff": float(np.max(np.abs(mu_b - mu_s)))}


# ---------------------------------------------------------------------------
# incremental archive loads
# ---------------------------------------------------------------------------

def bench_loads(n_bundles: int, bundle: int, workroot: str) -> Dict:
    """Cost of the analyze-funnel read: full re-read vs cached/incremental."""
    import tempfile
    from repro.core.bundler import Bundler
    rng = np.random.default_rng(2)
    with tempfile.TemporaryDirectory(dir=workroot) as d:
        b = Bundler(d)
        for i in range(n_bundles):
            lo = i * bundle
            b.write_bundle(lo, lo + bundle, {
                "inputs": rng.random((bundle, 5)).astype(np.float32),
                "yield": rng.random(bundle).astype(np.float32)})
        cold = Bundler(d)
        t0 = time.perf_counter()
        cold.load_all()
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        cold.load_all()  # warm: unchanged tree, served from cache
        warm_s = time.perf_counter() - t0
        # incremental: one new bundle lands, only it is decompressed
        lo = n_bundles * bundle
        b.write_bundle(lo, lo + bundle, {
            "inputs": rng.random((bundle, 5)).astype(np.float32),
            "yield": rng.random(bundle).astype(np.float32)})
        t0 = time.perf_counter()
        cold.load_all()
        incr_s = time.perf_counter() - t0
    return {"bundles": n_bundles, "bundle": bundle,
            "cold_load_s": cold_s, "warm_load_s": warm_s,
            "incremental_load_s": incr_s,
            "warm_speedup": cold_s / max(warm_s, 1e-9)}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run(quick: bool = False, out: str = DEFAULT_OUT, workroot: str = None,
        n_tasks: int = None, max_bundle: int = None, sur_rows: int = None,
        sur_steps: int = None, load_bundles: int = None,
        xb_samples: int = None, xb_bundle: int = None,
        mesh_tasks: int = None, mesh_bundle: int = None,
        with_mesh: bool = True) -> Dict:
    """Explicit size kwargs override the quick/full presets (the slow-marked
    smoke test runs everything tiny so the bench itself cannot rot)."""
    import tempfile
    import jax

    workroot = workroot or tempfile.gettempdir()
    n_tasks = n_tasks or (24 if quick else 96)
    max_bundle = max_bundle or (16 if quick else 48)
    results = {
        "meta": {
            "bench": "ensemble_throughput",
            "quick": quick,
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "unix_time": time.time(),
        },
        **bench_bundles(n_tasks, max_bundle, workroot),
        "engine_xbatch": bench_engine_xbatch(
            n_samples=xb_samples or (192 if quick else 384),
            bundle=xb_bundle or 4, workroot=workroot),
        # 128 rows ≈ the loop's archive after 2–3 iterations of batch 48
        "surrogate": bench_surrogate(n_rows=sur_rows or (64 if quick else 128),
                                     steps=sur_steps or (100 if quick else 300),
                                     repeats=2 if quick else 3),
        "loads": bench_loads(n_bundles=load_bundles or (20 if quick else 100),
                             bundle=16, workroot=workroot),
    }
    if with_mesh:
        results["mesh_dispatch"] = bench_mesh_dispatch(
            n_tasks=mesh_tasks or (6 if quick else 16),
            bundle=mesh_bundle or 32)
    md = results.get("mesh_dispatch", {})
    results["acceptance"] = {
        # PR 5 bar: the shared engine's cross-worker coalescing must at
        # least double samples/s over per-worker coalescing on the same
        # ragged workload with the same 4-worker/batch-4 fleet
        "engine_xbatch_speedup": results["engine_xbatch"]["speedup"],
        "pass_xbatch": results["engine_xbatch"]["speedup"] >= 2.0,
        # ... and shard_map dispatch must be exactly equivalent (IEEE-exact
        # sim bit-for-bit; JAG within last-ULP codegen variance) within
        # the bucketed compile bound.  None = scenario did not run.
        "mesh_bit_equal": bool(md.get("bit_equal", False)),
        "pass_mesh": bool(
            md.get("bit_equal", False)
            and md.get("jag_max_rel_diff", 1.0) <= 1e-3
            and md.get("exact_sharded", {}).get("traces", 1 << 30)
            <= md.get("bucket_bound", 0)) if md else None,
    }
    results["acceptance"]["pass"] = bool(
        results["acceptance"]["pass_xbatch"]
        and results["acceptance"]["pass_mesh"] is not False)
    if out:
        tmp = out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(results, f, indent=2)
        os.rename(tmp, out)
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="where to write BENCH_ensemble.json ('' to skip)")
    ap.add_argument("--mesh-worker", default=None, metavar="JSON",
                    help=argparse.SUPPRESS)  # internal: forced-device child
    args = ap.parse_args()
    if args.mesh_worker is not None:
        mesh_worker_main(json.loads(args.mesh_worker))
        return
    r = run(quick=args.quick, out=args.out or None)
    for scen in ("ragged", "uniform"):
        row = r[scen]
        print(f"{scen}: {row['baseline']['samples_per_s']:.0f} -> "
              f"{row['fused']['samples_per_s']:.0f} samples/s "
              f"({row['speedup']:.1f}x); compiles "
              f"{row['baseline']['traces']} -> {row['fused']['traces']} "
              f"(bound {row['bucket_bound']})")
    xb = r["engine_xbatch"]
    print(f"engine_xbatch: {xb['per_worker']['samples_per_s']:.0f} -> "
          f"{xb['xbatch']['samples_per_s']:.0f} samples/s "
          f"({xb['speedup']:.2f}x, bar >= 2x); launches "
          f"{xb['per_worker']['launches']} -> {xb['xbatch']['launches']}")
    md = r.get("mesh_dispatch", {})
    if md:
        print(f"mesh_dispatch: {md['devices']} devices, bit_equal="
              f"{md['bit_equal']}, jag max rel diff "
              f"{md['jag_max_rel_diff']:.1e}, sharded traces "
              f"{md['exact_sharded']['traces']} + "
              f"{md['jag_sharded']['traces']} (bound {md['bucket_bound']} "
              f"each), {md['jag_sharded']['samples_per_s']:.0f} samples/s "
              f"vs {md['jag_single']['samples_per_s']:.0f} single")
    s = r["surrogate"]
    print(f"surrogate: {s['baseline_s']:.2f}s -> {s['scanned_s']:.2f}s "
          f"({s['speedup']:.1f}x), max |Δmu|={s['prediction_max_abs_diff']:.2e}")
    ld = r["loads"]
    print(f"loads: cold {ld['cold_load_s']*1e3:.1f}ms, warm "
          f"{ld['warm_load_s']*1e3:.2f}ms, +1 bundle "
          f"{ld['incremental_load_s']*1e3:.2f}ms")
    if args.out:
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
