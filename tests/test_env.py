"""Process rules that keep one process per chip and no hidden fallback.

* importing the simulators starts no JAX backend (a process that only
  imports them must not take the accelerator);
* ``repro.env.configure()`` puts the persistent compilation cache where
  ``JAX_COMPILATION_CACHE_DIR`` says, else at one fixed in-checkout path;
* the ensemble bench's forced-host-device child is pinned to the CPU and
  its failure raises.

Each JAX check runs in a fresh ``JAX_PLATFORMS=cpu`` subprocess: backend
start-up and ``configure()`` are once-per-process effects.
"""
import json
import os
import subprocess
import sys

import pytest

from repro import env as repro_env

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code: str, **env_overrides) -> dict:
    """Run ``code`` in a fresh interpreter; it prints one JSON line last."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(REPO_ROOT, "src"),
                                           REPO_ROOT]))
    env.update({k: v for k, v in env_overrides.items() if v is not None})
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO_ROOT,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_importing_simulators_starts_no_backend():
    out = _run_py(
        "import json\n"
        "import repro.sim, repro.core, repro.core.active\n"
        "from jax._src import xla_bridge as xb\n"
        "print(json.dumps({'started': xb.backends_are_initialized()}))\n")
    assert out == {"started": False}


_REPORT_CACHE = (
    "import json\n"
    "from repro import env\n"
    "snap = env.configure()\n"
    "import jax\n"
    "print(json.dumps({'snapshot': snap['compilation_cache_dir'],\n"
    "                  'jax': jax.config.jax_compilation_cache_dir}))\n")


def test_default_cache_dir_is_one_fixed_path_across_processes():
    first = _run_py(_REPORT_CACHE)
    second = _run_py(_REPORT_CACHE)
    want = os.path.join(REPO_ROOT, ".jax_cache")
    assert first == second == {"snapshot": want, "jax": want}
    assert repro_env.DEFAULT_CACHE_DIR == want


def test_default_cache_dir_applies_when_jax_was_imported_first():
    out = _run_py("import jax\n" + _REPORT_CACHE)
    want = os.path.join(REPO_ROOT, ".jax_cache")
    assert out == {"snapshot": want, "jax": want}


def test_exported_cache_dir_is_used_and_receives_the_entries(tmp_path):
    cache = str(tmp_path / "cache")
    out = _run_py(
        _REPORT_CACHE +
        "import os, jax.numpy as jnp\n"
        "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()\n"
        "print(json.dumps({'entries': len(os.listdir(os.environ['JAX_COMPILATION_CACHE_DIR']))}))\n",
        JAX_COMPILATION_CACHE_DIR=cache,
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert out["entries"] > 0
    first = _run_py(_REPORT_CACHE, JAX_COMPILATION_CACHE_DIR=cache)
    assert first == {"snapshot": cache, "jax": cache}


def test_mesh_child_is_pinned_to_cpu_and_its_failure_raises(monkeypatch):
    from benchmarks import ensemble_throughput as ET
    seen = {}

    def failing_run(cmd, **kw):
        seen.update(kw["env"])
        return subprocess.CompletedProcess(cmd, 3, stdout="",
                                           stderr="no device")

    monkeypatch.setattr(subprocess, "run", failing_run)
    with pytest.raises(RuntimeError, match="mesh worker failed"):
        ET.bench_mesh_dispatch(n_tasks=1, bundle=8, devices=4)
    assert seen["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in seen["XLA_FLAGS"]
