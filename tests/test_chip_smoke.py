"""``chip_smoke.py`` on the CPU: its phases at a tiny size, its comparison
rule, and its refusal to run without a TPU.

The script itself only runs on a TPU; these tests drive the same phase
functions here so the smoke cannot rot between chip runs.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke as C  # noqa: E402


def _env(**extra):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_max_excess_flags_nan_pattern_and_tolerance():
    ref = {"y": np.array([1.0, 2.0, np.nan, 1e-9], np.float32)}
    same = {"y": ref["y"] * (1 + 5e-4)}
    assert C.max_excess(same, ref, rtol=1e-3, floor=1e-6)[0] <= 1.0
    off = {"y": ref["y"] * (1 + 5e-3)}
    assert C.max_excess(off, ref, rtol=1e-3, floor=1e-6)[0] > 1.0
    # near-zero entries are held to the floor, not to their own size
    tiny = {"y": np.array([1.0, 2.0, np.nan, 1.5e-6], np.float32)}
    assert C.max_excess(tiny, ref, rtol=1e-3, floor=1e-6)[0] <= 1.0
    with pytest.raises(C.SmokeFailure, match="non-finite pattern"):
        C.max_excess({"y": np.array([1.0, 2.0, 3.0, 0.0])}, ref, 1e-3, 1e-6)


def test_main_path_phases_pass_at_a_tiny_size():
    out = C.main_path(n_samples=2048, bundle=256, n_ref=32,
                      snapshot_kw={"n_members": 2, "hidden": 16,
                                   "steps": 40})
    assert out["samples"] == 2048 and out["launches"] <= 8
    assert out["ref_excess"] <= 1.0 and out["r2"] >= C.R2_BAR
    gw = out["gateway"]
    assert gw["completed"] == gw["inference_requests"] == 36
    assert gw["http_status"] == {"200": 37}


def test_four_chip_path_on_forced_host_devices():
    code = ("import json, chip_smoke as C\n"
            "print(json.dumps(C.four_chip_path(n_samples=2048, bundle=256)))")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO_ROOT, capture_output=True,
        text=True, timeout=300,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["bit_equal"] is True and out["launches"] >= 1


def test_script_refuses_to_run_without_a_tpu():
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO_ROOT,
                          capture_output=True, text=True, timeout=120,
                          env=_env())
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
