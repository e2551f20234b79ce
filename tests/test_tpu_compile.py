"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: each test lowers a jitted program at its real size and
compiles it for a ``v5e:2x2`` topology that is described, not attached, so
the chip's compiler refuses here (on the CPU host) what it would refuse on
the chip: unaligned kernel tiles, over-budget VMEM, a program that does
not fit, a collective that cannot be partitioned.  A compile that passes
is not a chip run.

The topology is described inside a module-scoped fixture (never at import,
in ``skipif`` or in ``parametrize``): only one process may load the TPU
library at a time, and every test worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import active
from repro.core.ensemble import EnsembleExecutor
from repro.sim import jag_simulate

SERVE_BUCKETS = [8, 16, 32, 64, 128, 256, 512]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), ("data",))


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A described-device compile is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **kwargs):
    compiled = fn.lower(*args, **kwargs).compile()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("rows", [1024, 65536])
def test_executor_bucket_program_one_chip(one_chip, rows):
    """The fused JAG bucket program: one leaf bundle and a whole study."""
    fn = EnsembleExecutor(jag_simulate, mesh=None)._build(rows)
    _compile(fn, _sds((rows, 5), jnp.float32, one_chip),
             _sds((rows,), jnp.uint32, one_chip))


def test_executor_shard_map_program_four_chips(mesh4):
    """The shard_map dispatch over a 4-chip 1-D mesh partitions."""
    rows = 65536
    ex = EnsembleExecutor(jag_simulate, mesh=mesh4)
    assert ex._mesh_divides(rows)
    spec = NamedSharding(mesh4, P("data"))
    compiled = _compile(ex._build(rows), _sds((rows, 5), jnp.float32, spec),
                        _sds((rows,), jnp.uint32, spec))
    out = compiled.output_shardings
    assert all(len(s.device_set) == 4 for s in jax.tree.leaves(out))


def _member_shapes(members: int, dims: int, hidden: int, one_chip):
    init = jax.vmap(lambda r: active._mlp_init(r, (dims, hidden, hidden, 1)))
    keys = jax.ShapeDtypeStruct((members, 2), jnp.uint32)
    return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                        jax.eval_shape(init, keys))


def test_surrogate_fit_program(one_chip):
    """``_fit_members`` at a 65,536-row archive, the merlin-serve
    defaults (3 members, 64 hidden, 300 steps)."""
    rows = 65536
    params = _member_shapes(3, 5, 64, one_chip)
    _compile(active._fit_members, params,
             _sds((rows, 5), jnp.float32, one_chip),
             _sds((rows,), jnp.float32, one_chip),
             _sds((rows,), jnp.float32, one_chip), steps=300, lr=3e-3)


@pytest.mark.parametrize("bucket", SERVE_BUCKETS)
def test_surrogate_apply_program(one_chip, bucket):
    """``_ensemble_apply`` at each bucket the gateway's batcher pads to."""
    params = _member_shapes(3, 5, 64, one_chip)
    _compile(active._ensemble_apply, params,
             _sds((bucket, 5), jnp.float32, one_chip))


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_flash_attention_kernel(one_chip):
    """(1, 2048, 8 q heads / 2 kv heads, 128) in bf16."""
    from repro.kernels import flash_attention as fak
    q = _sds((1, 2048, 8, 128), jnp.bfloat16, one_chip)
    kv = _sds((1, 2048, 2, 128), jnp.bfloat16, one_chip)
    assert _has_kernel(_compile(fak.flash_attention, q, kv, kv, causal=True))


def test_ssd_scan_kernel(one_chip):
    """zamba2-1.2b's Mamba2 mixer: 64 heads of 64, state 64, chunk 256."""
    from repro.kernels import ssd_scan as ssdk
    B, S, H, Pd, N = 1, 2048, 64, 64, 64
    fn = functools.partial(ssdk.ssd_scan, chunk=256)
    compiled = _compile(
        jax.jit(fn), _sds((B, S, H, Pd), jnp.bfloat16, one_chip),
        _sds((B, S, H), jnp.float32, one_chip),
        _sds((H,), jnp.float32, one_chip),
        _sds((B, S, N), jnp.bfloat16, one_chip),
        _sds((B, S, N), jnp.bfloat16, one_chip))
    assert _has_kernel(compiled)


def test_wkv6_scan_kernel(one_chip):
    """rwkv6-3b's time mix: 40 heads of 64, chunk 64."""
    from repro.kernels import wkv6_scan as wkvk
    B, S, H, D = 1, 2048, 40, 64
    x = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    fn = functools.partial(wkvk.wkv6_scan, chunk=64)
    compiled = _compile(jax.jit(fn), x, x, x,
                        _sds((B, S, H, D), jnp.float32, one_chip),
                        _sds((H, D), jnp.float32, one_chip))
    assert _has_kernel(compiled)
