"""Driver ``serve_open_loop``: open-loop traffic against the surrogate gateway.

Set-up builds the snapshot's JAG archive (``archive_samples`` rows drawn
from the seed and simulated on the chip), constructs a
``SurrogateSnapshot`` at the sizes of the cell's ``surrogate`` entry
(members, hidden width, ``fit_steps``, ``max_inflight``,
``max_batch_rows``, ``archive_samples``), installs ensemble
weights drawn from the seed (one jitted call on the chip, float32, the
type they are served in), starts ``SurrogateGateway``, compiles every
launch shape the batcher can use, sends one request of each kind over
HTTP, and starts the load generator (``chipbench/loadgen.py``, a stdlib
subprocess) with every request body built.

The window is the generator's schedule: ``rate`` x ``--seconds``
requests, due at Poisson-like times.  ``request_p95_ms`` is the 95th
percentile over all of them, each timed from its due time; a request with
no 200 ranks above every latency.  ``goodput_rps`` counts the 200s
answered within ``deadline_ms``.

The check compares the replies of a sample of requests drawn from the
seed (the largest predicts among them) with a plain forward pass of the
same weights in ``chipbench/reference/<config>.py``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

LOADGEN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "loadgen.py")
# near-zero replies (sigma where members agree) are compared against this
# share of the largest reply of their kind
REPLY_FLOOR = 1e-3


def make_weights(seed: int, members: int, dims: int, hidden: int):
    """Stacked member weights for a (dims, hidden, hidden, 1) MLP, drawn
    from the seed on the default device in one jitted call."""
    import jax

    shapes = [(dims, hidden), (hidden, hidden), (hidden, 1)]

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, 2 * len(shapes))
        return [{"w": jax.random.normal(keys[2 * i], (members,) + s)
                 * (2.0 / s[0]) ** 0.5,
                 "b": 0.1 * jax.random.normal(keys[2 * i + 1],
                                              (members, s[1]))}
                for i, s in enumerate(shapes)]

    return draw(jax.random.PRNGKey(seed % (2 ** 31)))


def build_gateway(run):
    """The snapshot and a started gateway, every launch shape compiled."""
    from repro.core import EnsembleExecutor
    from repro.core.active import Surrogate, SurrogateSnapshot
    from repro.core.bundler import Bundler
    from repro.serve.gateway import SurrogateGateway

    cfg = run.config
    sur = run.cell["surrogate"]
    n, d = int(sur["archive_samples"]), int(cfg["input_dims"])
    X = np.random.default_rng(run.seed).random((n, d), dtype=np.float32)
    out = EnsembleExecutor(run.simulator(), None,
                           mesh=None).run_bundle(0, n, X)
    root = os.path.join(run.workdir, "archive")
    Bundler(root).write_bundle(0, n, {k: out[k] for k in
                                      ("inputs", "yield", "failed")})
    snap = SurrogateSnapshot(root, n_members=sur["members"],
                             hidden=sur["hidden"],
                             steps=sur["fit_steps"])
    weights = make_weights(run.seed, sur["members"], d, sur["hidden"])
    snap._sur = Surrogate.from_stacked(weights, sur["members"])
    b = 1
    while b <= sur["max_batch_rows"]:
        snap.predict(np.zeros((b, d), np.float32))
        b *= 2
    gw = SurrogateGateway(snap, host="127.0.0.1", port=0,
                          max_inflight=sur["max_inflight"],
                          max_batch_rows=sur["max_batch_rows"]).start()
    host_weights = [{k: np.asarray(v) for k, v in layer.items()}
                    for layer in weights]
    return gw, host_weights


def _warm_http(gw, dims: int) -> None:
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=60)
    try:
        for path, body in (("/v1/predict", {"points": [[0.5] * dims] * 3}),
                           ("/v1/what-if", {"point": [0.5] * dims,
                                            "n_perturb": 64}),
                           ("/v1/calibrate", {"target": 0.5,
                                              "n_candidates": 256})):
            conn.request("POST", path, json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            resp.read()
            if resp.status != 200:
                raise RuntimeError(f"warm-up {path} answered {resp.status}")
    finally:
        conn.close()


def plan_for(run, gw, rate: float, seconds: float, out: str) -> dict:
    cell, cfg = run.cell, run.config
    return {"host": "127.0.0.1", "port": gw.port, "rate": rate,
            "seconds": seconds, "seed": run.seed,
            "deadline_ms": cell["deadline_ms"], "mix": cell["mix"],
            "predict_rows": cell["predict_rows"],
            "n_perturb": cell["n_perturb"],
            "n_candidates": cell["n_candidates"],
            "dims": int(cfg["input_dims"]), "threads": cell["threads"],
            "record": [], "out": out}


def start_loadgen(plan: dict, plan_path: str) -> subprocess.Popen:
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    proc = subprocess.Popen([sys.executable, LOADGEN, plan_path],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            text=True)
    if proc.stdout.readline().strip() != "ready":
        proc.kill()
        proc.wait()
        raise RuntimeError("load generator did not start")
    return proc


def finish_loadgen(proc: subprocess.Popen, plan: dict, timeout: float):
    try:
        proc.wait(timeout=timeout)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if proc.returncode != 0:
        raise RuntimeError(f"load generator exited {proc.returncode}")
    with open(plan["out"]) as f:
        return json.load(f)


def latencies(result: dict, deadline_ms: float) -> dict:
    """Per-request latency from due time (ms; inf without a 200), the
    200s within the deadline, and how late the generator sent."""
    lat, late, good = [], [], 0
    for _, due, sent, done, status in result["records"]:
        late.append((sent - due) * 1000.0)
        if status == 200:
            ms = (done - due) * 1000.0
            lat.append(ms)
            good += ms <= deadline_ms
        else:
            lat.append(float("inf"))
    span = max(r[3] for r in result["records"]) * 1000.0
    return {"latencies_ms": lat, "good": good, "lateness_ms": late,
            "span_ms": span}


def pick_records(run, kinds_sizes) -> list:
    """Requests whose replies are compared: the largest predicts and a
    sample of every kind, drawn from the seed."""
    rng = np.random.default_rng(run.seed + 2)
    picks = []
    for kind, count in run.cell["record"].items():
        idx = [i for i, (k, _) in enumerate(kinds_sizes) if k == kind]
        if kind == "predict":
            largest = sorted(idx, key=lambda i: -kinds_sizes[i][1])[:8]
            rest = [i for i in idx if i not in set(largest)]
            idx = largest + list(rng.permutation(rest)[:count - len(largest)])
        else:
            idx = list(rng.permutation(idx)[:count])
        picks += [int(i) for i in idx]
    return sorted(picks)


def reply_checks(ref, weights, replies: dict, dims: int) -> dict:
    """The replies against the reference's forward pass, as named fields
    for ``ref.gap``."""
    got, want = {}, {}

    def add(name, g, w):
        got.setdefault(name, []).extend(np.ravel(g))
        want.setdefault(name, []).extend(np.ravel(w))

    for r in replies.values():
        body, reply = r["body"], r["reply"]
        if r["path"] == "/v1/predict":
            mu, sd = ref.surrogate_apply(weights, np.asarray(body["points"],
                                                             np.float32))
            add("predict.mu", reply["mu"], mu)
            add("predict.sigma", reply["sigma"], sd)
        elif r["path"] == "/v1/calibrate":
            pts = np.asarray([c["point"] for c in reply["candidates"]],
                             np.float32)
            mu, sd = ref.surrogate_apply(weights, pts)
            add("calibrate.mu", [c["mu"] for c in reply["candidates"]], mu)
            add("calibrate.sigma", [c["sigma"] for c in reply["candidates"]],
                sd)
        else:
            base = np.asarray(body["point"], np.float32)
            cloud = np.clip(base[None, :] + np.random.default_rng(
                body["seed"]).normal(0.0, body["radius"],
                                     (body["n_perturb"], dims)),
                0.0, 1.0).astype(np.float32)
            mu, sd = ref.surrogate_apply(weights,
                                         np.concatenate([base[None], cloud]))
            nb = reply["neighborhood"]
            add("what_if.mu", reply["mu"], mu[0])
            add("what_if.sigma", reply["sigma"], sd[0])
            add("what_if.neighborhood",
                [nb["mu_mean"], nb["mu_min"], nb["mu_max"]],
                [mu[1:].mean(), mu[1:].min(), mu[1:].max()])
            add("what_if.spread", nb["mu_std"], mu[1:].std())
    return ref.gap({k: np.asarray(v) for k, v in got.items()},
                   {k: np.asarray(v) for k, v in want.items()},
                   floor=REPLY_FLOOR)


def run(run):
    cell, cfg = run.cell, run.config
    gw, weights = build_gateway(run)
    try:
        _warm_http(gw, int(cfg["input_dims"]))
        out = os.path.join(run.workdir, "loadgen.json")
        plan = plan_for(run, gw, cell["rate"], run.seconds, out)
        from chipbench.loadgen import schedule
        kinds, _, sizes = schedule(plan)
        plan["record"] = pick_records(run, list(zip(kinds, sizes)))
        proc = start_loadgen(plan, os.path.join(run.workdir, "plan.json"))
        b0 = gw.batcher.stats()
        t_open = run.window_opens()
        proc.stdin.write("go\n")
        proc.stdin.flush()
        if run.trace:
            time.sleep(max(0.0, t_open + cell["trace_at"] * run.seconds
                           - time.perf_counter()))
            run.trace_start()
            time.sleep(cell["trace_s"])
            run.trace_stop()
        result = finish_loadgen(proc, plan, run.seconds + 120)
        b1 = gw.batcher.stats()
        http = gw.stats()["http"]["status"]
    finally:
        gw.stop(drain=True)
    run.read_memory_peak()

    lat = latencies(result, cell["deadline_ms"])
    late = sorted(lat["lateness_ms"])
    print(f"loadgen lateness ms: p50 {late[len(late) // 2]!r} "
          f"p95 {late[int(0.95 * (len(late) - 1))]!r}; http status {http}",
          flush=True)
    statuses = [r[4] for r in result["records"]]
    unanswered = sum(s == 0 for s in statuses)
    wrong = sum(s not in (0, 200, 429, 504) for s in statuses)
    checks = {"unanswered": {"value": unanswered, "limit": 0},
              "error_replies": {"value": wrong, "limit": 0}}
    replies = result["replies"]
    checks["no_reply_compared"] = {"value": int(not replies), "limit": 0}
    if replies:
        res = reply_checks(run.reference, weights, replies,
                           int(cfg["input_dims"]))
        checks["nan_mismatch"] = {"value": res["nan_mismatch"], "limit": 0}
        checks["reply_gap"] = {"value": res["value_gap"],
                               "limit": run.reference.LIMITS["reply_gap"]}
    return {
        "kind": "serve_open_loop",
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(statuses),
        "failed": sum(s != 200 for s in statuses),
        "checks": checks,
        "window_s": run.seconds,
        "batcher": {k: b1[k] - b0[k] for k in b1
                    if isinstance(b1[k], (int, float)) and k in b0},
        **lat,
    }
