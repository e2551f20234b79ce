"""Chip smoke: drive the ML-ready ensemble path once on a TPU and check it.

    python chip_smoke.py               # one chip: the whole main path
    python chip_smoke.py --four-chips  # four chips: shard_map dispatch only

One process from start to finish (a process that touches JAX holds the
chip, so nothing here starts a second one).  The one-chip run goes through
the entry points a user calls, in order:

1. environment  ``repro.env.configure()``; the first device must be a TPU
                (no CPU fallback).
2. ensemble     a 65,536-sample JAG study through ``MerlinRuntime`` +
                ``WorkerPool(n_workers=4)`` with the default engine:
                64 leaf bundles of 1,024, each a fused
                ``EnsembleExecutor`` launch written by ``Bundler``.
3. reference    256 sample ids recomputed with ``vmap(jag_simulate)`` on
                the host CPU device, compared with the bundled values.
4. surrogate    ``train_surrogate`` on half of
                ``regression_dataset(data, "yield")``; held-out R^2.
5. gateway      ``SurrogateSnapshot`` at the ``merlin-serve`` defaults
                behind ``SurrogateGateway``, driven over HTTP from 4 threads.

``--four-chips`` runs the same study with the default executor (an auto
4-device mesh, ``shard_map`` dispatch) and compares it with one-chip
execution of the same sample blocks.

Every phase raises on failure; nothing is caught and carried on.  The last
line of standard output is the JSON contract line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)                        # benchmarks package
sys.path.insert(0, os.path.join(ROOT, "src"))   # repro package

import numpy as np  # noqa: E402

SAMPLES = 65536
BUNDLE = 1024
FANOUT = 8
WORKERS = 4
N_REF = 256
# JAG agreement bar (benchmarks/ensemble_throughput.py: rel 1e-3) plus an
# absolute floor, scaled by each field's peak, for near-zero image noise
# and Gaussian tails that a TPU may flush to zero
JAG_RTOL = 1e-3
JAG_FLOOR = 1e-6
# gateway replies vs snapshot.predict on the same rows: the batcher fuses
# requests into other padded shapes, so rows may take another tiling
SERVE_RTOL = 1e-3
SERVE_FLOOR = 1e-4
R2_BAR = 0.9


class SmokeFailure(RuntimeError):
    """A phase did not produce what it must."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def max_excess(got, ref, rtol: float, floor: float):
    """Compare two output dicts field by field.

    Returns ``(excess, max_rel)``: ``excess`` is the largest
    ``|got - ref| / (rtol * |ref| + floor * peak)`` over finite entries
    (<= 1 passes; ``peak`` is the field's largest finite magnitude), and
    ``max_rel`` the largest plain relative difference above the floor.
    Raises when the NaN patterns differ."""
    excess = max_rel = 0.0
    for k in ref:
        a = np.asarray(got[k], np.float64)
        b = np.asarray(ref[k], np.float64)
        check(a.shape == b.shape, f"{k}: shape {a.shape} != {b.shape}")
        nan_a, nan_b = ~np.isfinite(a), ~np.isfinite(b)
        check(np.array_equal(nan_a, nan_b),
              f"{k}: non-finite pattern differs in "
              f"{int((nan_a != nan_b).sum())} entries")
        fin = ~nan_b
        if not fin.any():
            continue
        a, b = a[fin], b[fin]
        peak = float(np.abs(b).max())
        diff = np.abs(a - b)
        bound = rtol * np.abs(b) + floor * peak
        with np.errstate(divide="ignore", invalid="ignore"):
            e = np.where(bound > 0, diff / bound, np.where(diff > 0, np.inf, 0))
        excess = max(excess, float(e.max()))
        above = np.abs(b) > floor * peak / rtol
        if above.any():
            max_rel = max(max_rel, float((diff[above] / np.abs(b[above])).max()))
    return excess, max_rel


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def run_study(ws: str, samples: np.ndarray, bundle: int, executor_kw=None):
    """The JAG study through runtime + pool + engine + executor + bundler.
    Returns ``(bundler, executor, pool_stats, seconds)``."""
    from repro.core import (Bundler, EnsembleExecutor, MerlinRuntime, Step,
                            StudySpec, WorkerPool)
    from repro.core.hierarchy import HierarchyCfg
    from repro.sim import jag_simulate

    rt = MerlinRuntime(workspace=ws,
                       hierarchy=HierarchyCfg(max_fanout=FANOUT, bundle=bundle))
    bundler = Bundler(os.path.join(ws, "results"))
    executor = EnsembleExecutor(jag_simulate, bundler, **(executor_kw or {}))
    rt.register("simulate", executor.step_fn())
    spec = StudySpec(name="chip-smoke",
                     steps=[Step(name="simulate", fn="simulate")])
    t0 = time.perf_counter()
    with WorkerPool(rt, n_workers=WORKERS) as pool:
        study = rt.run(spec, samples)
        done = rt.wait(study, timeout=900)
        stats = pool.stats()
    seconds = time.perf_counter() - t0
    check(done, f"study did not finish (pool stats {stats})")
    for k in ("failed", "dead_lettered", "skipped"):
        check(stats[k] == 0, f"pool counted {stats[k]} {k} tasks")
    return bundler, executor, stats, seconds


def reference_check(data, samples: np.ndarray, n_ref: int, seed: int = 0):
    """Recompute ``n_ref`` sample ids on the host CPU device and compare
    with the bundled values.  Returns ``(excess, max_rel, ids)``."""
    import jax
    import jax.numpy as jnp
    from repro.sim import jag_simulate

    n = len(samples)
    rng = np.random.default_rng(seed)
    failed_ids = np.flatnonzero(np.asarray(data["failed"]) > 0.5)
    ids = np.unique(np.concatenate([
        np.linspace(0, n - 1, n_ref - min(16, len(failed_ids))).astype(int),
        rng.permutation(failed_ids)[:16]]))
    check(np.array_equal(data["_sample_ids"][ids], ids),
          "bundled rows are not in sample-id order")
    cpu = jax.devices("cpu")[0]
    u = jax.device_put(samples[ids], cpu)
    seeds = jax.device_put(jnp.asarray(ids, jnp.uint32), cpu)
    ref = jax.jit(lambda u, s: jax.vmap(jag_simulate)(
        u, jax.vmap(jax.random.PRNGKey)(s)))(u, seeds)
    ref = {k: np.asarray(v) for k, v in ref.items()}
    got = {k: np.asarray(data[k])[ids] for k in ref}
    excess, max_rel = max_excess(got, ref, JAG_RTOL, JAG_FLOOR)
    return excess, max_rel, ids


def surrogate_fit(data):
    """``examples/quickstart.py`` step 3: fit half, score the other half."""
    from repro.core.active import train_surrogate
    from repro.data.pipeline import regression_dataset

    X, y = regression_dataset(data, target="yield")
    n = len(X)
    sur = train_surrogate(X[: n // 2], y[: n // 2], steps=400)
    mu, sd = sur.predict(X[n // 2:])
    check(np.isfinite(mu).all() and np.isfinite(sd).all(),
          "surrogate predicted non-finite values")
    r2 = 1.0 - float(np.mean((mu - y[n // 2:]) ** 2)) / float(np.var(y[n // 2:]))
    return r2, n // 2


def _request_plan(n_predict: int, dims: int, seed: int = 1):
    rng = np.random.default_rng(seed)
    plan = [("POST", "/v1/predict",
             {"points": rng.random((int(rng.integers(8, 257)), dims))
              .astype(np.float32).tolist()}) for _ in range(n_predict)]
    plan += [("POST", "/v1/calibrate",
              {"target": float(t), "n_candidates": 256, "seed": i})
             for i, t in enumerate((0.3, 0.7))]
    plan += [("POST", "/v1/what-if",
              {"point": rng.random(dims).tolist(), "radius": 0.05,
               "n_perturb": 64, "seed": i}) for i in range(2)]
    plan.append(("POST", "/v1/refresh", {}))
    return plan


def _finite(*vals) -> bool:
    return all(np.isfinite(np.asarray(v, np.float64)).all() for v in vals)


def gateway_phase(study_root: str, n_predict: int = 32, threads: int = 4,
                  **snapshot_kw):
    """Serve the study's snapshot over HTTP and check every reply.
    Returns a dict of counts for the log."""
    from repro.core.active import SurrogateSnapshot
    from repro.serve.gateway import SurrogateGateway

    t0 = time.perf_counter()
    snap = SurrogateSnapshot(study_root, **snapshot_kw)
    fit_s = time.perf_counter() - t0
    gw = SurrogateGateway(snap, host="127.0.0.1", port=0).start()
    local = threading.local()

    def send(job):
        method, path, body = job
        conn = getattr(local, "conn", None)
        if conn is None:
            conn = local.conn = http.client.HTTPConnection(
                "127.0.0.1", gw.port, timeout=300)
        conn.request(method, path, body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())

    plan = _request_plan(n_predict, snap.dims)
    try:
        with ThreadPoolExecutor(threads) as pool:
            replies = list(pool.map(send, plan))
        stats = gw.stats()
    finally:
        drained = gw.stop(drain=True)
    check(drained, "gateway did not drain")
    issued = 0
    worst = 0.0
    for (_, path, body), (status, out) in zip(plan, replies):
        check(status == 200, f"{path} answered {status}: {out}")
        if path == "/v1/predict":
            issued += 1
            check(_finite(out["mu"], out["sigma"]),
                  "predict reply has non-finite values")
            mu, sd = snap.predict(np.asarray(body["points"], np.float32))
            e, _ = max_excess({"mu": out["mu"], "sigma": out["sigma"]},
                              {"mu": mu, "sigma": sd}, SERVE_RTOL, SERVE_FLOOR)
            worst = max(worst, e)
        elif path == "/v1/calibrate":
            issued += 1
            check(all(_finite(c["mu"], c["sigma"]) for c in out["candidates"]),
                  "calibrate reply has non-finite values")
        elif path == "/v1/what-if":
            issued += 1
            check(_finite(out["mu"], out["sigma"],
                          list(out["neighborhood"].values())),
                  "what-if reply has non-finite values")
    check(worst <= 1.0, f"gateway replies differ from snapshot.predict "
                        f"(excess {worst:.3g} of the bound)")
    batcher = stats["batcher"]
    check(batcher["completed"] == issued,
          f"batcher completed {batcher['completed']} of {issued} issued")
    return {"requests": len(plan), "inference_requests": issued,
            "completed": batcher["completed"], "batches": batcher["batches"],
            "rows": snap.rows, "snapshot_fit_s": fit_s,
            "serve_excess": worst, "http_status": stats["http"]["status"]}


def main_path(n_samples: int = SAMPLES, bundle: int = BUNDLE,
              n_ref: int = N_REF, snapshot_kw=None) -> dict:
    """Phases 2-5 at the given size; raises on any failure."""
    import jax
    from repro.core import ensemble as E
    from repro.sim import jag_sample_inputs

    out = {}
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as ws:
        samples = np.asarray(jag_sample_inputs(jax.random.PRNGKey(0),
                                               n_samples))
        traces0 = E.trace_count()
        bundler, ex, pool_stats, study_s = run_study(ws, samples, bundle)
        t0 = time.perf_counter()
        data = bundler.load_all()
        load_s = time.perf_counter() - t0
        rows = len(data["_sample_ids"])
        check(rows == n_samples, f"archive holds {rows} of {n_samples} rows")
        n_bundles = math.ceil(n_samples / bundle)
        check(ex.stats["launches"] <= n_bundles,
              f"{ex.stats['launches']} launches for {n_bundles} bundles")
        log(f"ensemble: {rows}/{n_samples} samples bundled in {study_s:.3f}s "
            f"({n_bundles} bundles, {ex.stats['launches']} launches, "
            f"{E.trace_count() - traces0} traces); pool {pool_stats}; "
            f"archive load {load_s:.3f}s")
        out.update(study_s=study_s, samples=rows,
                   launches=ex.stats["launches"])

        t0 = time.perf_counter()
        excess, max_rel, ids = reference_check(data, samples, n_ref)
        ref_s = time.perf_counter() - t0
        check(excess <= 1.0,
              f"bundled values differ from the CPU reference "
              f"(excess {excess:.3g} of rel {JAG_RTOL} + {JAG_FLOOR} x peak)")
        log(f"reference: {len(ids)} ids vs CPU vmap(jag_simulate), "
            f"max rel diff {max_rel:.3e}, max excess {excess:.3e} of "
            f"tolerance (rel {JAG_RTOL} + {JAG_FLOOR} x field peak), "
            f"NaN/failed pattern identical; {ref_s:.3f}s")
        out.update(ref_max_rel=max_rel, ref_excess=excess)

        t0 = time.perf_counter()
        r2, n_train = surrogate_fit(data)
        fit_s = time.perf_counter() - t0
        check(r2 >= R2_BAR, f"held-out R^2 {r2:.4f} < {R2_BAR}")
        log(f"surrogate: held-out R^2 {r2:.4f} (n_train={n_train}) "
            f"in {fit_s:.3f}s")
        out.update(r2=r2, fit_s=fit_s)

        t0 = time.perf_counter()
        gw = gateway_phase(bundler.root, **(snapshot_kw or {}))
        gw_s = time.perf_counter() - t0
        log(f"gateway: {gw['requests']} requests all 200, "
            f"{gw['completed']}/{gw['inference_requests']} inference "
            f"requests completed in {gw['batches']} batches, snapshot "
            f"{gw['rows']} rows fit in {gw['snapshot_fit_s']:.3f}s, "
            f"reply excess {gw['serve_excess']:.3e}; {gw_s:.3f}s")
        out.update(gateway=gw, gateway_s=gw_s)
    return out


def four_chip_path(n_samples: int = SAMPLES, bundle: int = BUNDLE,
                   devices: int = 4) -> dict:
    """The study on the auto mesh vs the same blocks on one chip."""
    import jax
    from benchmarks.ensemble_throughput import _exact_sim_src
    from repro.core import ensemble as E
    from repro.sim import jag_sample_inputs, jag_simulate

    check(jax.local_device_count() == devices,
          f"need {devices} local devices, found {jax.local_device_count()}")
    samples = np.asarray(jag_sample_inputs(jax.random.PRNGKey(0), n_samples))
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as ws:
        traces0 = E.trace_count()
        bundler, ex, pool_stats, study_s = run_study(ws, samples, bundle)
        traces = E.trace_count() - traces0
        data = bundler.load_all()
    check(ex.stats["devices"] == devices,
          f"default executor spans {ex.stats['devices']} devices")
    check(len(data["_sample_ids"]) == n_samples, "archive is incomplete")
    # every launch of this study is >= one bundle, i.e. a bucket >= 4
    check(ex.stats["mesh_launches"] == ex.stats["launches"],
          f"{ex.stats['mesh_launches']} mesh launches of "
          f"{ex.stats['launches']}")
    # fused launches cover 1..n_bundles bundles: one bucket per power of two
    bound = int(math.log2(E.bucket_for(n_samples) // bundle)) + 1
    check(traces <= bound, f"{traces} traces > bucket bound {bound}")
    log(f"four-chip study: {n_samples} samples in {study_s:.3f}s, "
        f"{ex.stats['launches']} launches all on the mesh, {traces} traces "
        f"(bound {bound}); pool {pool_stats}")

    # the same blocks on one chip, and the sharding of a mesh launch
    single = E.EnsembleExecutor(jag_simulate, mesh=None)
    sharded = E.EnsembleExecutor(jag_simulate)
    dev_out = sharded.run_bundle(0, bundle, samples[:bundle], block=False)
    spans = len(dev_out["yield"].sharding.device_set)
    check(spans == devices, f"a mesh launch's output spans {spans} devices")
    worst = max_rel = 0.0
    for lo in range(0, n_samples, bundle):
        hi = min(lo + bundle, n_samples)
        one = single.run_bundle(lo, hi, samples[lo:hi])
        e, r = max_excess({k: data[k][lo:hi] for k in one}, one,
                          JAG_RTOL, JAG_FLOOR)
        worst, max_rel = max(worst, e), max(max_rel, r)
    check(worst <= 1.0, f"mesh results differ from one chip "
                        f"(excess {worst:.3g})")
    log(f"jag: mesh vs one chip over {n_samples} samples, max rel diff "
        f"{max_rel:.3e}, max excess {worst:.3e}; output sharding spans "
        f"{spans} devices")

    # IEEE-exact simulator: bit-for-bit, including a sub-mesh bucket
    exact = _exact_sim_src()
    sizes = [bundle] * 4 + [2]
    blocks = [np.random.default_rng(5).random((s, 5)).astype(np.float32)
              for s in sizes]
    runs = {}
    for tag, kw in (("single", {"mesh": None}), ("sharded", {})):
        xe = E.EnsembleExecutor(exact, **kw)
        lo, res = 0, []
        for blk in blocks:
            res.append(xe.run_bundle(lo, lo + len(blk), blk))
            lo += len(blk)
        runs[tag] = (res, xe.stats["mesh_launches"])
    expect_mesh = sum(1 for s in sizes if E.bucket_for(s) >= devices)
    check(runs["sharded"][1] == expect_mesh,
          f"{runs['sharded'][1]} mesh launches, expected {expect_mesh} "
          f"(buckets >= {devices})")
    check(runs["single"][1] == 0, "mesh=None executor used the mesh")
    bit_equal = all(np.array_equal(a[k], b[k], equal_nan=True)
                    for a, b in zip(runs["single"][0], runs["sharded"][0])
                    for k in a)
    check(bit_equal, "exact simulator: mesh results are not bit-equal")
    log(f"exact: bit-equal over sizes {sizes}, {runs['sharded'][1]} "
        f"mesh launches")
    return {"study_s": study_s, "launches": ex.stats["launches"],
            "traces": traces, "jag_max_rel": max_rel, "bit_equal": bit_equal}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip shard_map dispatch phase")
    args = ap.parse_args(argv)

    from repro import env as repro_env
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        # the reference phase needs the host CPU device next to the chip
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    t0 = time.perf_counter()
    env = repro_env.configure()
    import jax
    devs = jax.devices()
    dev = devs[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is {dev.platform!r} ({dev})")
    count = 4 if args.four_chips else 1
    check(len(devs) >= count, f"need {count} TPU devices, found {len(devs)}")
    log(f"environment: {dev.platform} {dev.device_kind!r} x{len(devs)}, "
        f"jax {jax.__version__}, compile cache "
        f"{env['compilation_cache_dir']}; {time.perf_counter() - t0:.3f}s")

    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_path(devices=count)
    else:
        main_path()
    log(f"total phase time {time.perf_counter() - t0:.3f}s")
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"device 0 peak_bytes_in_use {stats['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
