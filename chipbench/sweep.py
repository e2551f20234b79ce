"""Find the serve cell's knee once, by a sweep of open-loop rates.

    python3 chipbench/sweep.py --workload <serve cell> --seed 1 \
        --seconds 10 --rates 50,200,400,800,1200,1600

One process, one gateway: set-up as a run of the cell, then one window
per rate, lowest first.  The lowest rate runs without a deadline and fixes
``deadline_ms`` as 5 x its p95, rounded up to 10 ms; every other rate runs
with that deadline.  One JSON line per rate: p50/p95 from the due time,
goodput, the share of 200s, and the p95 of the last third of the window
against the first (a growing backlog shows as a last third far slower).
The knee is the highest rate whose p95 stays within the deadline without
a growing backlog; the cell runs at 0.8 x the knee.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _p(values, q):
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)] if v else float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import run as R
    R.configure_jax()
    cell = R.load_json(BENCH_DIR, "workloads", args.workload + ".json")
    config = R.load_json(BENCH_DIR, "configs", cell["config"] + ".json")
    devices = R.check_devices(1, allow_cpu=False)
    serve = R.load_module("drivers", "serve_open_loop")
    os.makedirs(R.WORK_DIR, exist_ok=True)
    work = tempfile.mkdtemp(prefix="sweep-", dir=R.WORK_DIR)
    run = R.Run(cell, config, args.seed, args.seconds, False, work, devices)
    gw, _ = serve.build_gateway(run)
    try:
        deadline = None
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            run.cell = dict(cell, deadline_ms=deadline or 1e6)
            out = os.path.join(work, f"rate{i}.json")
            plan = serve.plan_for(run, gw, rate, args.seconds, out)
            proc = serve.start_loadgen(plan, os.path.join(work, "plan.json"))
            proc.stdin.write("go\n")
            proc.stdin.flush()
            res = serve.finish_loadgen(proc, plan, args.seconds + 120)
            lat = serve.latencies(res, run.cell["deadline_ms"])
            ms = lat["latencies_ms"]
            third = len(ms) // 3
            p95 = _p(ms, 0.95)
            if deadline is None:
                deadline = 10 * math.ceil(5 * p95 / 10)
            print(json.dumps({
                "rate": rate, "deadline_ms": run.cell["deadline_ms"],
                "p50_ms": _p(ms, 0.5), "p95_ms": p95,
                "p95_first_third_ms": _p(ms[:third], 0.95),
                "p95_last_third_ms": _p(ms[-third:], 0.95),
                "goodput_rps": lat["good"] / args.seconds,
                "ok_share": sum(r[4] == 200 for r in res["records"]) / len(ms),
                "late_p95_ms": _p(lat["lateness_ms"], 0.95)}), flush=True)
        print(json.dumps({"deadline_ms": deadline}), flush=True)
    finally:
        gw.stop(drain=True)
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
