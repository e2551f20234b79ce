"""Dry-run machinery: HLO collective parsing (pure), plus one real
lower+compile cell in a 512-device subprocess (slow, but it is the
deliverable)."""
import json
import os
import subprocess
import sys

import pytest

# importing dryrun sets XLA_FLAGS=--xla_force_host_platform_device_count=512
# in THIS process (it must, before jax init, for its own `python -m` use).
# Restore the env around the import: the suite's contract (conftest.py) is
# that in-process tests see ONE device — leaking 512 would silently flip
# every later-initializing jax test (e.g. the ensemble auto-mesh) into a
# forced-multi-device process.
_saved_env = {k: os.environ.get(k) for k in ("XLA_FLAGS", "JAX_PLATFORMS")}
from repro.launch.dryrun import _shape_bytes, collective_bytes  # noqa: E402

for _k, _v in _saved_env.items():
    if _v is None:
        os.environ.pop(_k, None)
    else:
        os.environ[_k] = _v

HLO = """
ENTRY main {
  %p = f32[2048,512]{1,0} parameter(0)
  %ar = f32[2048,512]{1,0} all-reduce(f32[2048,512]{1,0} %p), replica_groups={}
  %ag = bf16[64,128]{1,0} all-gather(bf16[32,128]{1,0} %x), dimensions={0}
  %rs = f32[16]{0} reduce-scatter(f32[64]{0} %y), dimensions={0}
  %cp = u8[10]{0} collective-permute(u8[10]{0} %z), source_target_pairs={{0,1}}
  %dot = f32[4,4]{1,0} dot(f32[4,8]{1,0} %a, f32[8,4]{1,0} %b)
}
"""


def test_shape_bytes():
    assert _shape_bytes("f32[2048,512]") == 2048 * 512 * 4
    assert _shape_bytes("bf16[3]") == 6
    assert _shape_bytes("pred[]") == 1
    assert _shape_bytes("token[]") == 0


def test_collective_bytes_parses_operands():
    c = collective_bytes(HLO)
    assert c["all-reduce"] == 2048 * 512 * 4
    assert c["all-gather"] == 32 * 128 * 2
    assert c["reduce-scatter"] == 64 * 4
    assert c["collective-permute"] == 10
    assert c["all-to-all"] == 0
    assert c["count"] == 4


@pytest.mark.slow
def test_dryrun_cell_subprocess():
    """One real (arch x shape) cell through the 512-device dry-run."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "jag-surrogate", "--shape", "train_4k", "--out",
         "/tmp/dryrun_test.json"],
        env=env, capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    res = json.load(open("/tmp/dryrun_test.json"))[0]
    assert res["ok"]
    assert res["chips"] == 256
    assert res["flops"] > 0
    assert res["memory"]["temp_bytes"] > 0
    assert res["reconstructed"]["flops"] > res["flops"] * 0.5
