import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import: jax locks the device count at first init,
# and the 512 forced devices are host devices (never the accelerator).
# This flag lives ONLY here (and in subprocesses spawned from here) so smoke
# tests and benchmarks keep seeing one real device.
#
# Multi-pod dry-run: AOT lower + compile every (arch x shape) cell on the
# production mesh, prove it fits (memory_analysis) and extract the roofline
# inputs (cost_analysis + collective bytes parsed from the partitioned HLO).
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

import argparse
import json
import re
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.configs.base import SHAPES, ModelConfig, ShapeConfig, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.parallel import sharding as shd
from repro.serve.engine import make_decode_fn, make_prefill_fn, _extra_keys
from repro.train.optimizer import make_optimizer
from repro.train.trainstep import init_state, make_train_step

CACHE_DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# sharding trees
# ---------------------------------------------------------------------------

def _rules(arch: str, mesh) -> Dict:
    r = dict(shd.DEFAULT_RULES)
    over = registry.arch_rules(arch)
    if over:
        r.update(over)
    return {k: tuple(a for a in v if a in mesh.shape) for k, v in r.items()}


def batch_shardings(cfg: ModelConfig, specs: Dict[str, jax.ShapeDtypeStruct],
                    mesh, rules) -> Dict[str, NamedSharding]:
    out = {}
    for k, v in specs.items():
        logical = ("batch",) + (None,) * (len(v.shape) - 1)
        out[k] = NamedSharding(mesh, shd.spec_for(v.shape, logical, mesh, rules))
    return out


def cache_shardings(cfg: ModelConfig, caches_sds, mesh, rules):
    tp = rules.get("tensor", ())
    tp_size = 1
    for a in tp:
        tp_size *= mesh.shape[a]

    def f(path, leaf):
        keys = tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        name = keys[-1]
        shape = leaf.shape
        stacked = any(k == "blocks" for k in keys)
        rank = len(shape) - (1 if stacked else 0)
        if name in ("k", "v", "xk", "xv"):
            kvh = shape[-2]
            if tp_size > 1 and kvh % tp_size == 0:
                logical = ("batch", None, "kv_heads", None)
            else:
                logical = ("batch", "kv_seq", None, None)
        elif name in ("latent", "k_rope"):
            logical = ("batch", "kv_seq", None)
        elif name == "ssm":
            logical = ("batch", "heads", None, None)
        elif name == "state":
            logical = ("batch", "heads", None, None)
        elif name == "conv":
            logical = ("batch", None, "tensor")
        elif name in ("shift_t", "shift_c"):
            logical = ("batch", None, None)
        else:  # pos and misc scalars
            logical = (None,) * rank
        if stacked:
            logical = (None,) + tuple(logical)
        logical = logical[:len(shape)]
        return NamedSharding(mesh, shd.spec_for(shape, logical, mesh, rules))

    return jax.tree_util.tree_map_with_path(f, caches_sds)


# ---------------------------------------------------------------------------
# lowering per workload kind
# ---------------------------------------------------------------------------

def lower_cell(arch: str, shape_name: str, mesh, cfg: Optional[ModelConfig] = None):
    cfg = cfg or registry.get_config(arch)
    shape = SHAPES[shape_name]
    rules = _rules(arch, mesh)
    specs = registry.input_specs(cfg, shape, abstract=True)
    b_sh = batch_shardings(cfg, specs, mesh, rules)

    params_sds = jax.eval_shape(
        lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    params_sh = shd.param_spec_tree(params_sds, mesh,
                                    registry.arch_rules(arch))

    if shape.kind == "train":
        opt = make_optimizer(cfg.optimizer)
        state_sds = jax.eval_shape(
            lambda: init_state(jax.random.PRNGKey(0), cfg, opt))
        state_sh = type(state_sds)(
            params_sh,
            _opt_shardings(state_sds.opt, mesh, rules),
            NamedSharding(mesh, P()))
        step = make_train_step(cfg, opt, mesh=mesh, rules=registry.arch_rules(arch))
        jitted = jax.jit(step, in_shardings=(state_sh, b_sh),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))
        lowered = jitted.lower(state_sds, specs)
    elif shape.kind == "prefill":
        fn = make_prefill_fn(cfg, max_len=shape.seq_len, mesh=mesh,
                             rules=registry.arch_rules(arch),
                             cache_dtype=CACHE_DTYPE)
        args = [specs["tokens"]] + [specs[k] for k in _extra_keys(cfg)]
        in_sh = [b_sh["tokens"]] + [b_sh[k] for k in _extra_keys(cfg)]
        jitted = jax.jit(fn, in_shardings=(params_sh, *in_sh))
        lowered = jitted.lower(params_sds, *args)
    else:  # decode
        caches_sds = jax.eval_shape(
            lambda: lm.init_caches(cfg, shape.global_batch, shape.seq_len,
                                   CACHE_DTYPE))
        caches_sh = cache_shardings(cfg, caches_sds, mesh, rules)
        fn = make_decode_fn(cfg, mesh=mesh, rules=registry.arch_rules(arch))
        jitted = jax.jit(fn, in_shardings=(params_sh, b_sh["token"], caches_sh),
                         out_shardings=(None, caches_sh),
                         donate_argnums=(2,))
        lowered = jitted.lower(params_sds, specs["token"], caches_sds)
    return lowered


def _opt_shardings(opt_sds, mesh, rules):
    def f(path, leaf):
        keys = tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        return NamedSharding(mesh, shd.param_spec(keys, leaf.shape, mesh, rules))
    return jax.tree_util.tree_map_with_path(f, opt_sds)


# ---------------------------------------------------------------------------
# analysis extraction
# ---------------------------------------------------------------------------

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(tok: str) -> int:
    m = _SHAPE_RE.match(tok)
    if not m:
        return 0
    dt, dims = m.group(1), m.group(2)
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes of every collective op in (partitioned) HLO text."""
    out = {k: 0 for k in COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        for kind in COLLECTIVES:
            # match "= TYPE[dims] kind(" or "kind-start("
            m = re.search(rf"=\s+(\S+)\s+{kind}(?:-start)?\(([^)]*)\)", s)
            if m is None:
                continue
            operands = m.group(2)
            b = sum(_shape_bytes(t) for t in re.findall(r"\w+\[[\d,]*\]",
                                                        operands))
            if b == 0:  # operand list may omit shapes; use result shape
                b = _shape_bytes(m.group(1).split("(")[0])
                # tuple results: sum inner shapes
                if b == 0:
                    b = sum(_shape_bytes(t) for t in
                            re.findall(r"\w+\[[\d,]*\]", m.group(1)))
            out[kind] += b
            out["count"] += 1
            break
    return out


def analyze(lowered, compile_=True) -> Dict[str, Any]:
    info: Dict[str, Any] = {}
    t0 = time.time()
    compiled = lowered.compile()
    info["compile_s"] = round(time.time() - t0, 1)
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    info["flops"] = float(ca.get("flops", 0.0))
    info["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            info["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "generated_code_bytes": int(ma.generated_code_size_in_bytes),
                "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
            }
    except Exception as e:  # pragma: no cover
        info["memory_error"] = str(e)
    info["collectives"] = collective_bytes(compiled.as_text())
    return info


def _probe_cfg(cfg: ModelConfig, n_rep: int) -> ModelConfig:
    """Reduced-depth probe for loop-trip-count reconstruction.

    XLA's cost_analysis counts a while-loop (lax.scan) body ONCE, so the
    scanned-layer flops/bytes/collectives must be reconstructed: probe with
    n_repeat=1 and 2 (microbatch=1), take the delta as the per-superblock
    cost, and extrapolate to the true depth.  Enc-dec configs scale the
    encoder depth alongside so its scan is reconstructed too.
    """
    over = {"n_repeat": n_rep, "microbatch": 1, "scan_unroll": True,
            "n_layers": len(cfg.prologue) + len(cfg.superblock) * n_rep}
    if cfg.n_enc_layers:
        over["n_enc_layers"] = n_rep
    return cfg.replace(**over)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             cfg: Optional[ModelConfig] = None,
             skip_probes: bool = False) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    cfg = cfg or registry.get_config(arch)
    t0 = time.time()
    lowered = lower_cell(arch, shape_name, mesh, cfg=cfg)
    res = {"arch": arch, "shape": shape_name,
           "mesh": "x".join(str(s) for s in mesh.devices.shape),
           "chips": n_chips, "lower_s": round(time.time() - t0, 1)}
    res.update(analyze(lowered))

    if not skip_probes:
        p1 = analyze(lower_cell(arch, shape_name, mesh, cfg=_probe_cfg(cfg, 1)))
        p2 = analyze(lower_cell(arch, shape_name, mesh, cfg=_probe_cfg(cfg, 2)))
        reps = cfg.n_repeat
        rec = {}
        for key in ("flops", "bytes_accessed"):
            delta = p2[key] - p1[key]
            rec[key] = p1[key] + delta * (reps - 1)
        coll = {}
        for k in COLLECTIVES:
            delta = p2["collectives"][k] - p1["collectives"][k]
            coll[k] = int(p1["collectives"][k] + delta * (reps - 1))
        rec["collectives"] = coll
        rec["probe_compile_s"] = p1["compile_s"] + p2["compile_s"]
        # decomposition: base (embed/logits/loss/optimizer) vs per-superblock
        rec["base_flops"] = 2 * p1["flops"] - p2["flops"]
        rec["layer_flops"] = p2["flops"] - p1["flops"]
        rec["base_bytes"] = 2 * p1["bytes_accessed"] - p2["bytes_accessed"]
        rec["layer_bytes"] = p2["bytes_accessed"] - p1["bytes_accessed"]
        res["reconstructed"] = rec
    res.update(model_flops_info(cfg, SHAPES[shape_name]))
    return res


def model_flops_info(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Analytic MODEL_FLOPS = 6·N_active·D (train) / 2·N_active·D (inference),
    the 'useful compute' yardstick for the roofline table."""
    params_sds = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    total = 0
    expert = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_sds)[0]:
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        if any("experts" in str(getattr(k, "key", k)) for k in path):
            expert += n
    n_active = total - expert
    if cfg.n_experts:
        n_active += expert * cfg.top_k / cfg.n_experts
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6 if shape.kind == "train" else 2
    return {"n_params": int(total), "n_active_params": int(n_active),
            "model_flops": float(mult * n_active * tokens)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", type=str, default=None)
    ap.add_argument("--override", type=str, default=None,
                    help="JSON dict of ModelConfig overrides (perf iteration)")
    ap.add_argument("--cache-dtype", type=str, default=None,
                    help="decode cache dtype override (e.g. int8 KV)")
    args = ap.parse_args(argv)

    if args.cache_dtype:
        global CACHE_DTYPE
        CACHE_DTYPE = jnp.dtype(args.cache_dtype)
    overrides = json.loads(args.override) if args.override else None

    cells = []
    if args.all:
        for arch in registry.ARCHS:
            if arch == "jag-surrogate":
                continue
            cfg = registry.get_config(arch)
            for s in SHAPES.values():
                if shape_applicable(arch, s.name, cfg.family):
                    cells.append((arch, s.name))
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape)]

    results = []
    for arch, shape in cells:
        print(f"=== dry-run {arch} x {shape} "
              f"({'multi-pod 2x16x16' if args.multi_pod else 'single-pod 16x16'}) ===",
              flush=True)
        try:
            cfg = None
            if overrides:
                cfg = registry.get_config(arch).replace(**overrides)
            res = run_cell(arch, shape, args.multi_pod, cfg=cfg)
            if overrides:
                res["overrides"] = overrides
            res["ok"] = True
            print(json.dumps(res, indent=1), flush=True)
        except Exception as e:
            res = {"arch": arch, "shape": shape, "ok": False,
                   "error": f"{type(e).__name__}: {e}"[:2000]}
            print("FAILED:", res["error"], flush=True)
        results.append(res)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    nok = sum(r["ok"] for r in results)
    print(f"\n{nok}/{len(results)} cells passed")
    return 0 if nok == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
