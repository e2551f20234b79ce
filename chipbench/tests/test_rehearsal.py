"""Both drivers and both references end to end on the CPU at a tiny size,
with the look for a chip skipped, and the same runs with the timed path
broken underneath, where ``correct`` must come out false.

Each run happens in a temporary copy of the checkout to which the test
adds a throwaway configuration, workload and metric as new files plus
entries in BENCHMARK.json, as a later change would: no existing file of the
harness is edited.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HERE = os.path.dirname(os.path.abspath(__file__))

TINY = {  # throwaway cell -> (cell file it copies, config, overrides)
    "jag_tiny.b64": ("workloads/jag_icf.b10.json", "jag_tiny",
                     {"bundle": 64, "check_files": 4,
                      "check_ids_per_file": 16, "trace_s": 0.5}),
    "jag_tiny.b64.4chip": ("workloads/jag_icf.b10.4chip.json", "jag_tiny",
                           {"bundle": 64, "check_files": 4,
                            "check_ids_per_file": 16, "trace_s": 0.5}),
    "seir_tiny.b32": ("workloads/seir_covid.b32.json", "seir_tiny",
                      {"check_files": 4, "check_ids_per_file": 16,
                       "trace_s": 0.5}),
    # no serving cell is in BENCHMARK.json yet; the test's own template
    "jag_tiny.serve": (os.path.join(HERE, "serve_cell.json"), "jag_tiny", {}),
}
# the study rows at the simulator's own small sizes
CONFIGS = {"jag_tiny": ("jag_icf", {
               "n_samples": 1 << 13,
               "module_sizes": {"repro.sim.jag": {"N_T": 32, "IMG": 16,
                                                  "N_VIEWS": 4}},
               "outputs": {**{k: [] for k in (
                   "yield", "tion", "velocity", "rhor", "pressure",
                   "adiabat", "mix", "bang_time", "burn_width", "shape_deg",
                   "failed")}, "burn_rate": [32], "tion_trace": [32],
                   "images": [4, 16, 16], "inputs": [5]}}),
           "seir_tiny": ("seir_covid", {"n_samples": 1 << 13})}

# faults planted in the program under the harness; each must make
# ``correct`` false
FAULTS = {
    "none": "",
    # a study answer altered where it is produced
    "study_answer": """
import repro.sim
_sim = repro.sim.jag_simulate
def _altered(u, rng):
    out = dict(_sim(u, rng))
    out["yield"] = out["yield"] * 1.03
    return out
repro.sim.jag_simulate = _altered
""",
    # half of every bundle left out of the file that is acked
    "study_half_batch": """
from repro.core.bundler import Bundler
_write = Bundler.write_bundle
def _half(self, lo, hi, results):
    mid = lo + (hi - lo) // 2
    return _write(self, lo, mid, {k: v[:mid - lo] for k, v in results.items()})
Bundler.write_bundle = _half
""",
    # on a mesh, the exchange between chips left out: each chip keeps its
    # rows and the host reads chip 0's block in every chip's place
    "study_no_exchange": """
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.ensemble import EnsembleExecutor
_build = EnsembleExecutor._build
def _local(self, n):
    if not self._mesh_divides(n):
        return _build(self, n)
    ndev = int(self.mesh.shape[self.data_axis])
    def run(batch, seeds):
        rngs = jax.vmap(jax.random.PRNGKey)(seeds)
        return jax.vmap(self.simulator)(batch, rngs)
    local = jax.shard_map(run, mesh=self.mesh, in_specs=(P(self.data_axis),) * 2,
                          out_specs=P(), check_vma=False)
    return jax.jit(lambda b, s: jax.tree.map(
        lambda a: jnp.concatenate([a] * ndev), local(b, s)))
EnsembleExecutor._build = _local
""",
    # a served answer altered where it is produced
    "serve_answer": """
from repro.core.active import SurrogateSnapshot
_predict = SurrogateSnapshot.predict
def _altered(self, X):
    mu, sd = _predict(self, X)
    return mu * 1.03, sd
SurrogateSnapshot.predict = _altered
""",
    # the second half of every fused batch answered with the first half's rows
    "serve_half_batch": """
import numpy as np
from repro.core.active import Surrogate
_predict = Surrogate.predict
def _half(self, X):
    X = np.asarray(X, np.float32)
    h = max(1, (len(X) + 1) // 2)
    mu, sd = _predict(self, np.concatenate([X[:h], X[:len(X) - h]]))
    return mu, sd
Surrogate.predict = _half
""",
}

WRAPPER = """
import sys
sys.path[:0] = [{copy!r}, {src!r}]
{fault}
from chipbench import run
sys.exit(run.main({argv!r}, allow_cpu=True))
"""


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    dst = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "chipbench"), dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench_dir = dst / "chipbench"
    for name, (base, over) in CONFIGS.items():
        cfg = json.loads((bench_dir / "configs" / f"{base}.json").read_text())
        cfg.update(over, name=name)
        (bench_dir / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        shutil.copy(bench_dir / "reference" / f"{base}.py",
                    bench_dir / "reference" / f"{name}.py")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, (base, cfg, over) in TINY.items():
        cell = json.loads((bench_dir / base).read_text())
        cell.update(over, config=cfg)
        (bench_dir / "workloads" / f"{name}.json").write_text(json.dumps(cell))
        bench["workloads"].append({"name": name, "config": cfg,
                                   "traffic": name.split(".", 1)[1],
                                   "chips": cell["chips"],
                                   "why": "throwaway CPU cell"})
        # a study cell reports what the proven study cell reports
        like = "jag_icf.b10" if cell["driver"] == "study_backlog" else name
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(name)
    # the serve metrics, as a change that adds a serving cell adds them
    for group, name, unit in (("end_to_end", "request_p95_ms", "ms"),
                              ("end_to_end", "goodput_rps", "requests/s"),
                              ("per_layer", "batch_requests", "requests")):
        if all(m["name"] != name for m in bench[group]):
            entry = {"name": name, "unit": unit, "better": "lower",
                     "source": "host_clock", "workloads": ["jag_tiny.serve"]}
            if group == "end_to_end":
                entry["bound"] = 0.25
            else:
                entry.update(source="program_counter", layer="batcher",
                             moves="request_p95_ms")
            bench[group].append(entry)
    # a throwaway per-layer metric: a reader file and an entry
    (bench_dir / "metrics" / "acked_tasks.py").write_text(
        "def read(r):\n    return r.get('engine', {}).get('executed')\n")
    bench["per_layer"].append({"name": "acked_tasks", "unit": "tasks",
                               "better": "higher", "source": "program_counter",
                               "layer": "ExecutionEngine",
                               "moves": "samples_per_s",
                               "workloads": ["jag_tiny.b64"]})
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def run_cell(copy, cell, fault="none", trace=0, seed=2 ** 31 + 12345):
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", "2",
            "--trace", str(trace)]
    code = WRAPPER.format(copy=str(copy), src=os.path.join(ROOT, "src"),
                          fault=FAULTS[fault], argv=argv)
    chips = json.loads((copy / "chipbench" / "workloads" /
                        f"{cell}.json").read_text())["chips"]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    p = subprocess.run([sys.executable, "-c", code], cwd=copy, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert not os.listdir(copy / ".chipbench_work")  # the run cleaned up
    return line


@pytest.mark.parametrize("cell,trace", [("jag_tiny.b64", 1),
                                        ("jag_tiny.b64.4chip", 0),
                                        ("seir_tiny.b32", 0),
                                        ("jag_tiny.serve", 0)])
def test_tiny_cell_runs_correct(copy, cell, trace):
    line = run_cell(copy, cell, trace=trace)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == (4 if cell.endswith("4chip") else 1)
    metrics = line["metrics"]
    if trace:
        assert {"engine_batch_tasks", "pad_share", "window_traces",
                "acked_tasks"} <= set(metrics)
        assert metrics["window_traces"]["value"] == 0
    else:
        assert "setup_s" in metrics and len(metrics) >= 2


@pytest.mark.parametrize("cell,fault,check", [
    ("jag_tiny.b64", "study_answer", "value_gap"),
    ("jag_tiny.b64", "study_half_batch", "missing_ids"),
    ("jag_tiny.b64.4chip", "study_no_exchange", "value_gap"),
    ("jag_tiny.serve", "serve_answer", "reply_gap"),
    ("jag_tiny.serve", "serve_half_batch", "reply_gap"),
])
def test_broken_timed_path_is_not_correct(copy, cell, fault, check):
    line = run_cell(copy, cell, fault=fault)
    assert line["correct"] is False
    c = line["checks"][check]
    assert c["value"] > c["limit"], line["checks"]
