"""Open-loop HTTP load generator for the surrogate gateway (stdlib only).

    python3 chipbench/loadgen.py PLAN.json

It never imports JAX or numpy, so it does not fight the server for a chip
or for the server's interpreter lock.  The plan gives the gateway's
address, the rate, the window, the request mix and the seed.  Every seed
gets the same set of requests (kinds, sizes and arrival gaps), in another
order: gaps are the quantiles of an exponential distribution at the rate,
predict sizes the quantiles of a log-uniform distribution, the kinds
exact shares of the mix.

All request bodies are built before the window.  The generator then prints
``ready``, waits for ``go`` on standard input, and sends each request at
its due time from a pool of keep-alive connections, whether or not earlier
replies have come back.  A request's latency runs from its due time, so a
stall in the server also counts against the requests queued behind it.

It writes PLAN["out"]: one record ``[kind, due_s, sent_s, done_s, status]``
per request (status 0: no reply), and the bodies and replies of the
requests listed in PLAN["record"].
"""
from __future__ import annotations

import http.client
import json
import math
import queue
import random
import socket
import sys
import threading
import time


def schedule(plan: dict):
    """(kinds, gaps, predict sizes) for the plan: a fixed multiset per
    rate and window, shuffled by the seed."""
    rng = random.Random(plan["seed"])
    n = max(1, round(plan["rate"] * plan["seconds"]))
    mix = plan["mix"]
    counts = {k: int(n * share) for k, share in mix.items()}
    rest = sorted(mix, key=lambda k: n * mix[k] - counts[k], reverse=True)
    for k in rest[:n - sum(counts.values())]:
        counts[k] += 1
    kinds = [k for k, c in counts.items() for _ in range(c)]
    rng.shuffle(kinds)
    gaps = [-math.log(1.0 - (i + 0.5) / n) / plan["rate"] for i in range(n)]
    rng.shuffle(gaps)
    lo, hi = plan["predict_rows"]
    sizes = [max(lo, min(hi, round(math.exp(
        math.log(lo) + (i + 0.5) / n * (math.log(hi + 1) - math.log(lo))
    ) - 0.5))) for i in range(n)]
    rng.shuffle(sizes)
    return kinds, gaps, sizes


def bodies(plan: dict, kinds, sizes):
    """(path, JSON body) per request, from the seed."""
    rng = random.Random(plan["seed"] + 1)
    dims, out = plan["dims"], []
    for i, kind in enumerate(kinds):
        body = {"deadline_ms": plan["deadline_ms"]}
        if kind == "predict":
            path = "/v1/predict"
            body["points"] = [[rng.random() for _ in range(dims)]
                              for _ in range(sizes[i])]
        elif kind == "what_if":
            path = "/v1/what-if"
            body.update(point=[rng.random() for _ in range(dims)],
                        radius=0.05, n_perturb=plan["n_perturb"], seed=i)
        else:
            path = "/v1/calibrate"
            body.update(target=rng.random(),
                        n_candidates=plan["n_candidates"], seed=i)
        out.append((path, json.dumps(body)))
    return out


def _connect(host, port):
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.connect()
    conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return conn


def main(plan_path: str) -> int:
    with open(plan_path) as f:
        plan = json.load(f)
    kinds, gaps, sizes = schedule(plan)
    reqs = bodies(plan, kinds, sizes)
    record = set(plan["record"])
    n = len(reqs)
    due, at = [], 0.0
    for g in gaps:
        at += g
        due.append(at)
    scale = plan["seconds"] / at  # the last request is due at the close
    due = [d * scale for d in due]
    sent, done, status = [0.0] * n, [0.0] * n, [0] * n
    replies = {}
    todo: "queue.Queue" = queue.Queue()
    host, port = plan["host"], plan["port"]
    conns = [_connect(host, port) for _ in range(plan["threads"])]

    def sender(conn):
        while True:
            i = todo.get()
            if i is None:
                return
            path, blob = reqs[i]
            sent[i] = time.monotonic() - t0
            try:
                conn.request("POST", path, blob,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                data = resp.read()
                status[i] = resp.status
            except (OSError, http.client.HTTPException):
                conn.close()
                conn = _connect(host, port)
                data = b""
            done[i] = time.monotonic() - t0
            if i in record and status[i] == 200:
                replies[i] = {"path": path, "body": json.loads(blob),
                              "reply": json.loads(data)}

    print("ready", flush=True)
    if sys.stdin.readline().strip() != "go":
        return 1
    t0 = time.monotonic()
    threads = [threading.Thread(target=sender, args=(c,)) for c in conns]
    for t in threads:
        t.start()
    for i in range(n):
        wait = due[i] - (time.monotonic() - t0)
        if wait > 0:
            time.sleep(wait)
        todo.put(i)
    for _ in threads:
        todo.put(None)
    for t in threads:
        t.join()
    for c in conns:
        c.close()
    with open(plan["out"], "w") as f:
        json.dump({"records": [[kinds[i], due[i], sent[i], done[i], status[i]]
                               for i in range(n)],
                   "replies": {str(i): r for i, r in replies.items()}}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
