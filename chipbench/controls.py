"""Control readings: the plain reference, put in the program's place and
computed in bfloat16, compared as a run compares the program.

    python3 chipbench/controls.py --workload <cell> --seeds 1,2,3

For each seed it draws what a run of the cell draws (inputs, request
bodies, weights), takes the compared sample the way the run's check takes
it, and prints the compared numbers of the bfloat16 reference against the
float32 reference, one JSON line per seed.  Every number must come out
above its limit: a limit that a bfloat16 computation passes would let a
program that drops to bfloat16 pass too.  The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def study_sample(cell: dict, config: dict, ref, seed: int):
    """Sample ids and inputs as a study run's check draws them: files among
    the first bundles a window acks, their failed shots first."""
    n, d = int(config["n_samples"]), int(config["input_dims"])
    inputs = np.random.default_rng(seed).random((n, d), dtype=np.float32)
    rng = np.random.default_rng(seed + 1)
    bundle = int(cell["bundle"])
    files = rng.permutation(min(200, n // bundle))[:int(cell["check_files"])]
    ids = []
    for f in sorted(files):
        file_ids = np.arange(f * bundle, (f + 1) * bundle)
        rows = rng.permutation(np.flatnonzero(~ref.ambiguous(inputs[file_ids])))
        ids.append(file_ids[np.sort(rows[:int(cell["check_ids_per_file"])])])
    ids = np.concatenate(ids)
    return ids, inputs[ids]


def study_control(cell, config, ref, seed: int) -> dict:
    ids, u = study_sample(cell, config, ref, seed)
    return ref.gap(ref.simulate(ids, u, "bfloat16"), ref.simulate(ids, u))


def serve_control(cell, config, ref, seed: int) -> dict:
    """The sampled requests of a serve run, answered by the bfloat16
    reference in the gateway's place."""
    from types import SimpleNamespace

    from chipbench import loadgen
    from chipbench.run import load_module
    serve = load_module("drivers", "serve_open_loop")
    run = SimpleNamespace(cell=cell, config=config, seed=seed)
    dims = int(config["input_dims"])
    plan = serve.plan_for(run, SimpleNamespace(port=0), cell["rate"],
                          20.0, "")
    kinds, _, sizes = loadgen.schedule(plan)
    bodies = loadgen.bodies(plan, kinds, sizes)
    sur = cell["surrogate"]
    weights = [{k: np.asarray(v) for k, v in layer.items()} for layer in
               serve.make_weights(seed, sur["members"], dims, sur["hidden"])]
    replies = {}
    for i in serve.pick_records(run, list(zip(kinds, sizes))):
        path, blob = bodies[i]
        body = json.loads(blob)
        replies[str(i)] = {"path": path, "body": body,
                           "reply": _answer(ref, weights, path, body, dims)}
    return serve.reply_checks(ref, weights, replies, dims)


def _answer(ref, weights, path, body, dims):
    """What the gateway answers, computed by the bfloat16 reference."""
    def apply(X):
        return ref.surrogate_apply(weights, X, "bfloat16")
    if path == "/v1/predict":
        mu, sd = apply(np.asarray(body["points"], np.float32))
        return {"mu": mu.tolist(), "sigma": sd.tolist()}
    if path == "/v1/calibrate":
        cand = np.random.default_rng(body["seed"]).random(
            (body["n_candidates"], dims), np.float32)
        mu, sd = apply(cand)
        order = np.argsort(np.abs(mu - body["target"]), kind="stable")[:4]
        return {"candidates": [{"point": cand[i].tolist(), "mu": mu[i],
                                "sigma": sd[i]} for i in order]}
    base = np.asarray(body["point"], np.float32)
    cloud = np.clip(base[None, :] + np.random.default_rng(body["seed"]).normal(
        0.0, body["radius"], (body["n_perturb"], dims)), 0.0, 1.0)
    mu, sd = apply(np.concatenate([base[None], cloud]).astype(np.float32))
    nb = mu[1:]
    return {"mu": mu[0], "sigma": sd[0],
            "neighborhood": {"mu_mean": nb.mean(), "mu_std": nb.std(),
                             "mu_min": nb.min(), "mu_max": nb.max()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import run as R
    cell = R.load_json(BENCH_DIR, "workloads", args.workload + ".json")
    config = R.load_json(BENCH_DIR, "configs", cell["config"] + ".json")
    ref = R.load_module("reference", config["name"])
    control = serve_control if cell["driver"] == "serve_open_loop" \
        else study_control
    for seed in (int(s) for s in args.seeds.split(",")):
        res = control(cell, config, ref, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": "bfloat16", **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
