"""Schema fences for the BENCH_*.json perf-trajectory artifacts.

``benchmarks/run.py --quick --check-schema`` (CI's smoke path) validates
the artifacts right after writing them, so a refactor that silently stops
emitting a scenario — or emits NaNs/strings where throughput numbers
belong — fails the build instead of rotting the perf trajectory.

The specs are deliberately *minimal* required shapes: extra keys are
always allowed (benches grow), missing/mistyped required ones are errors.
A spec node is either a type tuple (leaf), a dict (required sub-keys), or
a callable predicate returning an error string or None.
"""
from __future__ import annotations

import json
import math
import os
from typing import Any, Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_NUM = (int, float)


def _finite(x: Any) -> bool:
    return isinstance(x, _NUM) and not isinstance(x, bool) \
        and math.isfinite(x)


def _check_node(doc: Any, spec: Any, path: str, errors: List[str]) -> None:
    if callable(spec) and not isinstance(spec, type):
        msg = spec(doc)
        if msg:
            errors.append(f"{path}: {msg}")
        return
    if isinstance(spec, dict):
        if not isinstance(doc, dict):
            errors.append(f"{path}: expected object, got {type(doc).__name__}")
            return
        for key, sub in spec.items():
            if key not in doc:
                errors.append(f"{path}.{key}: missing")
            else:
                _check_node(doc[key], sub, f"{path}.{key}", errors)
        return
    # leaf: type tuple, with numbers required finite
    if spec is _NUM or spec == _NUM:
        if not _finite(doc):
            errors.append(f"{path}: expected finite number, got {doc!r}")
    elif not isinstance(doc, spec):
        errors.append(f"{path}: expected {spec}, got {type(doc).__name__}")


_STREAM = {"tasks": _NUM, "samples": _NUM, "wall_s": _NUM,
           "samples_per_s": _NUM, "traces": _NUM}

_BUNDLE_SCENARIO = {"max_bundle": _NUM, "baseline": _STREAM,
                    "fused": _STREAM, "speedup": _NUM, "bucket_bound": _NUM}

_XBATCH_MODE = {"wall_s": _NUM, "samples_per_s": _NUM, "launches": _NUM}


def _mesh_spec(doc: Any) -> Optional[str]:
    """mesh_dispatch is the full result: a failed child raises in the
    bench instead of leaving a placeholder."""
    if not isinstance(doc, dict):
        return f"expected object, got {type(doc).__name__}"
    errs: List[str] = []
    _check_node(doc, {
        "devices": _NUM, "bucket_bound": _NUM, "bit_equal": bool,
        "jag_max_rel_diff": _NUM,
        "exact_single": {"wall_s": _NUM, "traces": _NUM},
        "exact_sharded": {"wall_s": _NUM, "traces": _NUM,
                          "mesh_launches": _NUM},
        "jag_single": {"samples_per_s": _NUM},
        "jag_sharded": {"samples_per_s": _NUM, "mesh_launches": _NUM},
    }, "", errs)
    return "; ".join(errs) if errs else None


ENSEMBLE_SPEC: Dict[str, Any] = {
    "meta": {"bench": str, "quick": bool, "jax": str, "backend": str,
             "unix_time": _NUM},
    "ragged": _BUNDLE_SCENARIO,
    "uniform": _BUNDLE_SCENARIO,
    "engine_xbatch": {"n_samples": _NUM, "tasks": _NUM, "bundle": _NUM,
                      "workers": _NUM, "batch": _NUM,
                      "per_worker": _XBATCH_MODE, "xbatch": _XBATCH_MODE,
                      "speedup": _NUM},
    "mesh_dispatch": _mesh_spec,
    "surrogate": {"rows": _NUM, "steps": _NUM, "baseline_s": _NUM,
                  "scanned_s": _NUM, "scanned_cold_s": _NUM,
                  "speedup": _NUM, "prediction_max_abs_diff": _NUM},
    "loads": {"bundles": _NUM, "bundle": _NUM, "cold_load_s": _NUM,
              "warm_load_s": _NUM, "incremental_load_s": _NUM,
              "warm_speedup": _NUM},
    "acceptance": {"engine_xbatch_speedup": _NUM, "pass_xbatch": bool,
                   "pass": bool},
}

# the codec A/B pair and the same-host transport scenarios must be
# present by name: a refactor that silently drops one would leave the
# wire-codec acceptance unmeasured while the artifact still "passes"
_REQUIRED_BROKER_SCENARIOS = ("net_mem_arr_w1_b32_bin1",
                              "net_mem_arr_w1_b32_json",
                              "net_mem_procs4_b8", "shm_w4_b8",
                              "elastic_rebalance")


def _broker_scenarios(d: Any) -> Optional[str]:
    if not (isinstance(d, dict) and d):
        return "expected a non-empty scenarios object"
    bad = [k for k, v in d.items()
           if not (isinstance(v, dict) and _finite(v.get("tasks_per_s"))
                   and _finite(v.get("wall_s")))]
    if bad:
        return f"scenarios need finite tasks_per_s and wall_s: {bad}"
    missing = [k for k in _REQUIRED_BROKER_SCENARIOS if k not in d]
    if missing:
        return f"required scenarios missing: {missing}"
    return None


BROKER_SPEC: Dict[str, Any] = {
    # meta.codec = the wire codec the scenarios were measured under;
    # meta.env = the applied runtime environment (repro/env.py snapshot)
    # — perf numbers are only comparable when both are recorded
    "meta": {"bench": str, "tasks": _NUM, "quick": bool, "unix_time": _NUM,
             "codec": str, "env": dict,
             "study_wall": {"bin1_s": _NUM, "json_s": _NUM,
                            "delta_s": _NUM}},
    "scenarios": _broker_scenarios,
    "file_index_speedup_vs_seed": _NUM,
    "acceptance": {"net_batched_vs_file_w1_b1": _NUM, "pass_net": bool,
                   "shard2_vs_net_mem_b8": _NUM, "pass_shard": bool,
                   "bin1_vs_json_arr_b32": _NUM, "pass_codec": bool,
                   "shm_vs_net_mem_procs4_b8": _NUM, "pass_shm": bool,
                   "elastic_moved_fraction": _NUM,
                   "elastic_moved_bar": _NUM,
                   "elastic_rebalance_s": _NUM,
                   "elastic_task_loss": _NUM,
                   "pass_elastic": bool,
                   "pass": bool},
}


# all three serving scenarios must be present by name: dropping the
# naive baseline (or the overload run) would leave the continuous-
# batching acceptance ratio and the shed gate unmeasured
_REQUIRED_SERVE_SCENARIOS = ("continuous", "naive", "overload_shed")


def _serve_scenarios(d: Any) -> Optional[str]:
    if not (isinstance(d, dict) and d):
        return "expected a non-empty scenarios object"
    errs: List[str] = []
    for name in _REQUIRED_SERVE_SCENARIOS:
        if name not in d:
            errs.append(f"required scenario missing: {name}")
            continue
        sc = d[name]
        if not isinstance(sc, dict):
            errs.append(f"{name}: expected object")
            continue
        for key in ("requests_per_s", "p50_ms", "p99_ms", "issued",
                    "completed", "shed", "expired", "other", "wall_s"):
            if not _finite(sc.get(key)):
                errs.append(f"{name}.{key}: expected finite number, "
                            f"got {sc.get(key)!r}")
        if not isinstance(sc.get("occupancy_hist"), dict):
            errs.append(f"{name}.occupancy_hist: expected object")
    return "; ".join(errs) if errs else None


SERVE_SPEC: Dict[str, Any] = {
    "meta": {"bench": str, "quick": bool, "unix_time": _NUM,
             "clients": _NUM, "requests_per_client": _NUM,
             "rows_per_request": _NUM, "env": dict},
    "scenarios": _serve_scenarios,
    "acceptance": {"continuous_vs_naive_rps": _NUM, "p99_ratio": _NUM,
                   "continuous_p99_ms": _NUM, "naive_p99_ms": _NUM,
                   "shed_rate": _NUM, "accounting_ok": bool,
                   "pass_throughput": bool, "pass_shed": bool,
                   "pass": bool},
}


def check_doc(doc: Any, spec: Dict[str, Any], name: str) -> List[str]:
    errors: List[str] = []
    _check_node(doc, spec, name, errors)
    return errors


def check_file(path: str, spec: Dict[str, Any]) -> List[str]:
    name = os.path.basename(path)
    try:
        with open(path) as f:
            doc = json.load(f)
    except FileNotFoundError:
        return [f"{name}: missing"]
    except json.JSONDecodeError as e:
        return [f"{name}: not valid JSON ({e})"]
    return check_doc(doc, spec, name)


def check_all(root: str = REPO_ROOT) -> List[str]:
    """Validate every artifact at the repo root; returns all errors."""
    return (check_file(os.path.join(root, "BENCH_ensemble.json"),
                       ENSEMBLE_SPEC)
            + check_file(os.path.join(root, "BENCH_broker.json"),
                         BROKER_SPEC)
            + check_file(os.path.join(root, "BENCH_serve.json"),
                         SERVE_SPEC))


if __name__ == "__main__":
    import sys
    errs = check_all()
    for e in errs:
        print(f"schema error: {e}", file=sys.stderr)
    if errs:
        sys.exit(1)
    print("BENCH_*.json schemas OK")
