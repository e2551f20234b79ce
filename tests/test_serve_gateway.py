"""Surrogate inference gateway: the HTTP status-mapping contract
(200/400/401/404/429/503/504), continuous batching end-to-end against a
real trained snapshot, snapshot refresh over the wire, graceful drain,
and the ``merlin-serve`` CLI as a subprocess with SIGINT shutdown.

Everything here opens localhost HTTP sockets, so the whole module
carries the ``serve`` marker (its own CI job; run with ``-m serve``)."""
import http.client
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core.active import SurrogateSnapshot
from repro.core.bundler import Bundler
from repro.serve.gateway import SurrogateGateway

pytestmark = pytest.mark.serve

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _request(port, method, path, body=None, headers=None, timeout=30.0):
    """One request, fresh connection; returns (status, parsed-json)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = None if body is None else (
            body if isinstance(body, bytes) else json.dumps(body))
        conn.request(method, path, body=payload,
                     headers={"Content-Type": "application/json",
                              **(headers or {})})
        r = conn.getresponse()
        raw = r.read()
        return r.status, (json.loads(raw) if raw else {})
    finally:
        conn.close()


def _post(port, path, body=None, **kw):
    return _request(port, "POST", path, body=body or {}, **kw)


def _get(port, path, **kw):
    return _request(port, "GET", path, **kw)


class _StubSnapshot:
    """Snapshot double for control-flow tests (no jax, instant)."""

    def __init__(self, block=False):
        self.version = 1
        self.rows = 8
        self.dims = 3
        self.gate = threading.Event()
        self.block = block
        self.calls = []  # row counts per fused launch

    def predict(self, X):
        first = not self.calls
        self.calls.append(len(X))
        if self.block and first:
            assert self.gate.wait(15.0)
        return (np.zeros(len(X), np.float32),
                np.ones(len(X), np.float32))

    def wait_entered(self):
        for _ in range(2000):
            if self.calls:
                return
            time.sleep(0.005)
        raise AssertionError("gateway never reached predict")

    def refresh(self):
        return False


def _archive(root, n=64, dims=3, seed=0):
    """A tiny study archive with enough signal to train on."""
    rng = np.random.default_rng(seed)
    X = rng.random((n, dims)).astype(np.float32)
    y = np.sin(3.0 * X[:, 0]) + 0.5 * X[:, 1]
    Bundler(root).write_bundle(0, n, {"inputs": X,
                                      "yield": y.astype(np.float32)})
    return X, y


def _tiny_snapshot(root):
    return SurrogateSnapshot(root, n_members=2, hidden=16, steps=40)


# ---------------------------------------------------------------------------
# end-to-end against a real trained snapshot
# ---------------------------------------------------------------------------

def test_predict_end_to_end(tmp_path):
    X, _ = _archive(str(tmp_path))
    with SurrogateGateway(_tiny_snapshot(str(tmp_path)),
                          auth_token=None) as gw:
        st, health = _get(gw.port, "/healthz")
        assert st == 200 and health["ok"] and health["rows"] == 64
        # 2-D batch
        st, out = _post(gw.port, "/v1/predict",
                        {"points": X[:4].tolist()})
        assert st == 200
        assert len(out["mu"]) == 4 and len(out["sigma"]) == 4
        assert all(np.isfinite(out["mu"])) and all(
            s >= 0 for s in out["sigma"])
        assert out["version"] == 1
        # 1-D point promotes to a single row
        st, out = _post(gw.port, "/v1/predict",
                        {"points": X[0].tolist()})
        assert st == 200 and out["n"] == 1
        st, stats = _get(gw.port, "/v1/stats")
        assert st == 200
        assert stats["batcher"]["completed"] >= 2
        assert stats["http"]["status"].get("200", 0) >= 3


def test_calibrate_and_what_if(tmp_path):
    _archive(str(tmp_path))
    with SurrogateGateway(_tiny_snapshot(str(tmp_path))) as gw:
        st, out = _post(gw.port, "/v1/calibrate",
                        {"target": 0.5, "n_candidates": 64, "top_k": 3,
                         "seed": 7})
        assert st == 200
        cands = out["candidates"]
        assert len(cands) == 3
        # gateway returns candidates best-first
        gaps = [c["gap"] for c in cands]
        assert gaps == sorted(gaps)
        assert all(len(c["point"]) == 3 for c in cands)

        st, out = _post(gw.port, "/v1/what-if",
                        {"point": [0.5, 0.5, 0.5], "radius": 0.05,
                         "n_perturb": 8})
        assert st == 200
        nb = out["neighborhood"]
        assert nb["mu_min"] <= out["mu"] + 1.0  # sane, finite geometry
        assert nb["mu_min"] <= nb["mu_mean"] <= nb["mu_max"]
        assert np.isfinite(out["sigma"])


def test_refresh_folds_new_bundles(tmp_path):
    root = str(tmp_path)
    _archive(root)
    snap = _tiny_snapshot(root)
    with SurrogateGateway(snap) as gw:
        st, out = _post(gw.port, "/v1/refresh")
        assert st == 200 and out["refreshed"] is False  # nothing new yet
        rng = np.random.default_rng(1)
        Xn = rng.random((32, 3)).astype(np.float32)
        Bundler(root).write_bundle(
            64, 96, {"inputs": Xn,
                     "yield": Xn[:, 0].astype(np.float32)})
        st, out = _post(gw.port, "/v1/refresh")
        assert st == 200 and out["refreshed"] is True
        assert out["rows"] == 96 and out["version"] == 2
        # the served model is the new one
        st, out = _post(gw.port, "/v1/predict", {"points": Xn[0].tolist()})
        assert st == 200 and out["version"] == 2


def _jag_archive(root, n=256, n_failed=12, seed=0):
    """A real JAG archive whose failed shots carry NaN yield."""
    import jax
    from repro.sim import jag_simulate
    rng = np.random.default_rng(seed)
    U = rng.random((n, 5)).astype(np.float32)
    # over-driven thin shells are the simulator's failure region: put
    # exactly n_failed rows there and keep the drive of the rest below it
    U[n_failed:, 0] *= 0.9
    U[:n_failed, 0] = 0.99
    U[:n_failed, 1] = 0.01
    keys = jax.vmap(jax.random.PRNGKey)(np.arange(n, dtype=np.uint32))
    out = {k: np.asarray(v)
           for k, v in jax.jit(jax.vmap(jag_simulate))(U, keys).items()}
    assert np.isnan(out["yield"]).sum() == n_failed
    out["yield"] = out["yield"] / 5e15  # keep the fit in a tame range
    Bundler(root).write_bundle(0, n, out)
    return U


def test_snapshot_skips_failed_shots(tmp_path):
    """One NaN row used to turn every prediction NaN: the snapshot trains
    only on finite, non-failed rows, and so does the served model."""
    U = _jag_archive(str(tmp_path))
    snap = _tiny_snapshot(str(tmp_path))
    assert snap.rows == 256 - 12
    mu, sd = snap.predict(U)
    assert np.isfinite(mu).all() and np.isfinite(sd).all()
    with SurrogateGateway(snap) as gw:
        st, out = _post(gw.port, "/v1/predict", {"points": U[:32].tolist()})
    assert st == 200
    assert np.isfinite(out["mu"]).all() and np.isfinite(out["sigma"]).all()


def test_snapshot_of_only_failed_shots_has_no_training_rows(tmp_path):
    _jag_archive(str(tmp_path), n=8, n_failed=8)
    with pytest.raises(ValueError, match="no training rows"):
        _tiny_snapshot(str(tmp_path))


# ---------------------------------------------------------------------------
# status-mapping contract (stub snapshot: no jax in the loop)
# ---------------------------------------------------------------------------

def test_bad_requests_get_400_and_unknown_routes_404():
    with SurrogateGateway(_StubSnapshot()) as gw:
        assert _post(gw.port, "/v1/predict", {})[0] == 400  # missing field
        assert _post(gw.port, "/v1/predict",
                     {"points": [[1, 2]]})[0] == 400  # wrong dims
        assert _post(gw.port, "/v1/predict",
                     {"points": [[1, 2, float("nan")]]})[0] == 400
        assert _post(gw.port, "/v1/predict",
                     body=b"{not json")[0] == 400
        assert _post(gw.port, "/v1/predict",
                     {"points": [[1, 2, 3]], "deadline_ms": -5})[0] == 400
        assert _post(gw.port, "/v1/nope", {})[0] == 404
        assert _get(gw.port, "/nope")[0] == 404
        # contract errors never reach the model
        assert _StubSnapshot.predict is not None
        assert gw.batcher.stats()["submitted"] == 0


def test_bearer_auth_guards_everything_but_healthz():
    with SurrogateGateway(_StubSnapshot(), auth_token="sekrit") as gw:
        ok = {"Authorization": "Bearer sekrit"}
        assert _get(gw.port, "/healthz")[0] == 200  # liveness stays open
        assert _post(gw.port, "/v1/predict",
                     {"points": [[1, 2, 3]]})[0] == 401
        assert _post(gw.port, "/v1/predict", {"points": [[1, 2, 3]]},
                     headers={"Authorization": "Bearer wrong"})[0] == 401
        assert _get(gw.port, "/v1/stats")[0] == 401
        st, _ = _post(gw.port, "/v1/predict", {"points": [[1, 2, 3]]},
                      headers=ok)
        assert st == 200
        assert _get(gw.port, "/v1/stats", headers=ok)[0] == 200


def test_shed_maps_to_429_with_retry_after():
    """max_inflight=1 with a launch in flight and one queued: the next
    request is shed before admission and told when to come back."""
    snap = _StubSnapshot(block=True)
    with SurrogateGateway(snap, max_inflight=1) as gw:
        results = []

        def post_one():
            results.append(_post(gw.port, "/v1/predict",
                                 {"points": [[1, 2, 3]]}))

        t1 = threading.Thread(target=post_one)
        t1.start()
        snap.wait_entered()  # t1's launch holds the batcher loop
        t2 = threading.Thread(target=post_one)
        t2.start()
        for _ in range(2000):  # wait until t2's request is queued
            if gw.batcher.stats()["queued"] >= 1:
                break
            time.sleep(0.005)
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=10)
        try:
            conn.request("POST", "/v1/predict",
                         body=json.dumps({"points": [[1, 2, 3]]}),
                         headers={"Content-Type": "application/json"})
            r = conn.getresponse()
            assert r.status == 429
            assert r.getheader("Retry-After") == "1"
            r.read()
        finally:
            conn.close()
        snap.gate.set()
        t1.join(timeout=15)
        t2.join(timeout=15)
        assert sorted(st for st, _ in results) == [200, 200]  # shed cost
        assert gw.batcher.stats()["shed"] == 1  # no admitted request


def test_deadline_maps_to_504_without_executing():
    snap = _StubSnapshot(block=True)
    with SurrogateGateway(snap) as gw:
        results = []

        def hold():
            results.append(_post(gw.port, "/v1/predict",
                                 {"points": [[1, 2, 3]]}))

        t1 = threading.Thread(target=hold)
        t1.start()
        snap.wait_entered()
        t2 = threading.Thread(target=lambda: results.append(
            _post(gw.port, "/v1/predict",
                  {"points": [[9, 9, 9]], "deadline_ms": 50})))
        t2.start()
        time.sleep(0.2)  # the 50ms deadline passes while queued
        snap.gate.set()
        t1.join(timeout=15)
        t2.join(timeout=15)
        statuses = sorted(st for st, _ in results)
        assert statuses == [200, 504]
        assert gw.batcher.stats()["expired"] == 1
        assert snap.calls == [1]  # the doomed rows never executed


def test_drain_returns_503_and_completes_admitted():
    """stop(drain=True): requests already admitted complete with 200
    while new arrivals are refused with 503."""
    snap = _StubSnapshot(block=True)
    gw = SurrogateGateway(snap).start()
    results = []

    def post_one():
        results.append(_post(gw.port, "/v1/predict",
                             {"points": [[1, 2, 3]]}))

    t1 = threading.Thread(target=post_one)
    t1.start()
    snap.wait_entered()
    t2 = threading.Thread(target=post_one)
    t2.start()
    for _ in range(2000):
        if gw.batcher.stats()["queued"] >= 1:
            break
        time.sleep(0.005)
    stopped = []
    stopper = threading.Thread(
        target=lambda: stopped.append(gw.stop(drain=True, timeout=15)))
    stopper.start()
    for _ in range(2000):  # draining flag flips before the drain wait
        if gw.stats()["draining"]:
            break
        time.sleep(0.005)
    st, body = _post(gw.port, "/v1/predict", {"points": [[1, 2, 3]]})
    assert st == 503 and "drain" in body["error"]
    snap.gate.set()
    t1.join(timeout=15)
    t2.join(timeout=15)
    stopper.join(timeout=20)
    assert stopped == [True]  # backlog fully drained
    assert sorted(s for s, _ in results) == [200, 200]


# ---------------------------------------------------------------------------
# merlin-serve CLI (subprocess, SIGINT drain)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_merlin_serve_cli_serves_and_drains_on_sigint(tmp_path):
    _archive(str(tmp_path / "study"))
    port_file = str(tmp_path / "serve.port")
    env = {**os.environ,
           "PYTHONPATH": "src" + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    env.pop("REPRO_AUTH_TOKEN", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "merlin-serve",
         "--study", str(tmp_path / "study"), "--port", "0",
         "--port-file", port_file,
         "--members", "2", "--hidden", "16", "--steps", "40"],
        cwd=REPO_ROOT, env=env, stdout=subprocess.PIPE, text=True)
    try:
        deadline = time.monotonic() + 120  # includes snapshot training
        while not os.path.exists(port_file):
            assert proc.poll() is None, "merlin-serve died during startup"
            assert time.monotonic() < deadline, "server did not come up"
            time.sleep(0.05)
        with open(port_file) as f:
            port = int(f.read())
        st, health = _get(port, "/healthz")
        assert st == 200 and health["rows"] == 64
        st, out = _post(port, "/v1/predict",
                        {"points": [[0.1, 0.2, 0.3]]})
        assert st == 200 and len(out["mu"]) == 1
        proc.send_signal(signal.SIGINT)
        stdout, _ = proc.communicate(timeout=30)
        assert proc.returncode == 0
        events = [json.loads(line) for line in stdout.splitlines()
                  if line.startswith("{")]
        kinds = [e["event"] for e in events]
        assert kinds[0] == "listening" and kinds[-1] == "drained"
        assert events[0]["mode"] == "continuous"
        assert events[-1]["clean"] is True
        assert events[-1]["stats"]["batcher"]["completed"] >= 1
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
