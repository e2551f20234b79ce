"""Device-fused ensemble execution — the TPU adaptation of Merlin's bundles.

On Sierra a Merlin "bundle" was 10 serial subprocess simulations per task
(Sec. 3.1); per-sample overhead ~33 ms (Fig. 5).  On a TPU/accelerator the
equivalent unit is a *vmapped batch*: a leaf task's [lo, hi) sample range is
executed as ONE jitted ``vmap(simulator)`` call, so the marginal per-sample
overhead is device-level, not process-level.  The hierarchy
(core/hierarchy.py) still generates the index space; only the leaf
execution is fused.

Multi-device dispatch
---------------------
On hosts exposing more than one device the executor defaults to a shared
1-D mesh (:func:`device_mesh`) and dispatches fused bundles with
``shard_map`` over the ``data`` axis: each device runs the vmapped
simulator on its contiguous slice of the padded batch.  The power-of-two
bucket schedule doubles as the sharding grid — any bucket >= the
(power-of-two) device count divides the mesh evenly, so no extra padding
logic exists for sharding; buckets smaller than the mesh fall back to
single-device jit.  Per-row independence makes the sharded result
bit-for-bit identical to the single-device one (regression-tested with
8 forced host devices), and the compile count stays within the same
bucketed bound: one trace per bucket, shard_mapped or not.

Bucketing policy
----------------
Ragged bundle sizes are the enemy of a jit cache: an optimization loop that
re-slices its batch every iteration produces O(#distinct sizes) distinct
``vmap`` shapes, each a fresh XLA compile.  ``run_bundle`` therefore pads
every batch up to the next power-of-two *bucket* (``bucket_for``) with
repeated edge rows and masked (don't-care) seeds, runs the compiled bucket
program, and slices the outputs back to the real ``[lo, hi)`` extent, so
the total number of compiles for any workload is O(log2 max_bundle), not
O(#distinct sizes).

Compile-cache policy
--------------------
The jit cache is **process-wide** by default: executors created for
different bundlers / iterations / studies share compiled programs keyed by
``(simulator, mesh, data_axis, bucket)``.  A fresh ``EnsembleExecutor`` per
task (the seed behavior) therefore no longer discards compiled code.  Pass
``share_cache=False`` to opt a specific executor out (used by benchmarks to
reproduce the pre-bucketing baseline).  ``trace_count()`` exposes a global
trace counter for compile-count regression tests.

Dispatch is async: the jitted call returns device futures; results are
synchronized (``jax.block_until_ready``) only when they must be
materialized — at bundler-write time, or when the caller asks for numpy
(``block=True``, the default).

``EnsembleExecutor.step_fn()`` returns a Merlin fn-step closure that runs
the simulator over ``ctx.sample_block`` and writes results through the
Bundler — i.e. the whole JAG workflow (Fig. 7) as one registered step.
Coalesced contexts (``ctx.sub_ranges``, core/runtime.py) execute as one
device launch but still publish one bundle file per original sub-task, so
the on-disk layout, crawl/resubmit granularity, and idempotency markers are
identical to per-task execution.
"""
from __future__ import annotations

import threading
import time
import weakref
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bundler import Bundler

# process-wide 1-D device mesh (multi-device dispatch) ------------------------
# Built lazily over ALL local devices with one "data" axis.  Fused bundles
# whose padded (power-of-two) size divides the device count dispatch via
# shard_map; smaller buckets fall back to the single-device jit — the bucket
# schedule is reused as the sharding grid, not duplicated.  Tests force a
# multi-device host with XLA_FLAGS=--xla_force_host_platform_device_count=8
# in a subprocess (the in-process suite keeps 1 device, see tests/conftest).
_DEVICE_MESH = None


def device_mesh(axis: str = "data"):
    """The shared 1-D mesh over this process's local devices; None on
    1-device hosts.  LOCAL devices only: on a multi-host jax.distributed
    deployment a global-device mesh would require every process to enter
    the launch collectively, which broker-driven workers never do."""
    global _DEVICE_MESH
    if jax.local_device_count() <= 1:
        return None
    if _DEVICE_MESH is None or _DEVICE_MESH.axis_names != (axis,):
        from jax.sharding import Mesh
        _DEVICE_MESH = Mesh(np.array(jax.local_devices()), (axis,))
    return _DEVICE_MESH

# process-wide compile cache + trace counter ---------------------------------
# Outer level is a WeakKeyDictionary on the simulator callable: per-study
# simulator closures (and the XLA executables compiled for them) are evicted
# when the last executor referencing them dies, so a long-lived worker
# process does not pin dead simulators forever.
_CACHE_LOCK = threading.Lock()
_SHARED_JIT: "weakref.WeakKeyDictionary[Callable, Dict[Tuple, Callable]]" = \
    weakref.WeakKeyDictionary()
_TRACE_COUNT = 0


def _count_trace() -> None:
    """Called from inside traced functions: runs once per (re)trace."""
    global _TRACE_COUNT
    _TRACE_COUNT += 1


def trace_count() -> int:
    """Total simulator traces (== XLA compiles) in this process so far."""
    return _TRACE_COUNT


def bucket_for(n: int) -> int:
    """Smallest power-of-two >= n: the padded batch size for a ragged n."""
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def bucket_schedule(max_n: int) -> List[int]:
    """All bucket sizes needed for bundles up to ``max_n`` (the compile
    bound asserted by the regression test: len == ceil(log2 max_n) + 1)."""
    out = [1]
    while out[-1] < max_n:
        out.append(out[-1] * 2)
    return out


def pad_rows(arr: np.ndarray, to: int) -> np.ndarray:
    """Pad a (n, ...) array to ``to`` rows by repeating the last row (keeps
    padded work numerically tame; outputs for pad rows are discarded)."""
    n = len(arr)
    if n == to:
        return arr
    reps = np.repeat(arr[-1:], to - n, axis=0)
    return np.concatenate([arr, reps], axis=0)


class EnsembleExecutor:
    def __init__(self, simulator: Callable, bundler: Optional[Bundler] = None,
                 mesh="auto", data_axis: str = "data", bucketed: bool = True,
                 share_cache: bool = True):
        """simulator: f(params_row: (d,) array, rng) -> dict of arrays.

        ``mesh="auto"`` (default) resolves to the process-wide 1-D
        :func:`device_mesh` when the host exposes more than one device
        (else single-device, exactly the old behavior); ``mesh=None``
        forces single-device; an explicit Mesh pins dispatch to it.
        """
        self.simulator = simulator
        self.bundler = bundler
        self.data_axis = data_axis
        self.mesh = device_mesh(data_axis) if mesh == "auto" else mesh
        self.bucketed = bucketed
        self.share_cache = share_cache
        self._private_jit: Dict[Tuple, Callable] = {}
        self.stats = {"bundles": 0, "samples": 0, "sim_time": 0.0,
                      "write_s": 0.0,
                      "compiles": 0, "launches": 0, "padded_samples": 0,
                      "mesh_launches": 0,
                      "devices": 1 if self.mesh is None
                      else int(self.mesh.shape[data_axis])}

    def _mesh_divides(self, n: int) -> bool:
        """True when size-n batches shard evenly over the mesh.  Power-of-
        two buckets >= a power-of-two device count always do, so the
        bucket padding doubles as the sharding grid; smaller buckets (or
        odd meshes) fall back to single-device dispatch."""
        return self.mesh is not None and \
            n % int(self.mesh.shape[self.data_axis]) == 0

    def _build(self, n: int) -> Callable:
        def run(batch, seeds):
            _count_trace()
            rngs = jax.vmap(jax.random.PRNGKey)(seeds)
            return jax.vmap(self.simulator)(batch, rngs)

        # donation frees the input buffers for reuse by the outputs; XLA on
        # CPU can't honor it and warns, so only donate on real accelerators
        donate = (0, 1) if jax.default_backend() != "cpu" else ()
        if self._mesh_divides(n):
            # shard_map over the 1-D data axis: each device runs the same
            # vmapped simulator on its n/ndev contiguous rows.  Rows are
            # independent (per-row rng from the row's seed), so the split
            # is bit-for-bit identical to the single-device vmap — the
            # multi-device equivalence test asserts exactly that.
            from jax.sharding import PartitionSpec as P
            spec = P(self.data_axis)
            sharded = jax.shard_map(run, mesh=self.mesh,
                                    in_specs=(spec, spec), out_specs=spec)
            return jax.jit(sharded, donate_argnums=donate)
        return jax.jit(run, donate_argnums=donate)

    def _compiled(self, n: int) -> Callable:
        """The jitted vmapped simulator for padded size n (cached; shared
        process-wide unless this executor opted out)."""
        key = (self.mesh, self.data_axis, n)
        if self.share_cache:
            with _CACHE_LOCK:
                per_sim = _SHARED_JIT.setdefault(self.simulator, {})
                fn = per_sim.get(key)
                if fn is None:
                    fn = per_sim[key] = self._build(n)
                    self.stats["compiles"] += 1
            return fn
        if key not in self._private_jit:
            self._private_jit[key] = self._build(n)
            self.stats["compiles"] += 1
        return self._private_jit[key]

    def run_bundle(self, lo: int, hi: int, samples: np.ndarray,
                   sub_ranges: Optional[Sequence[Tuple[int, int]]] = None,
                   block: bool = True, defer_write: bool = False):
        """Simulate samples [lo, hi) as one fused device launch.

        ``sub_ranges``: optional absolute [slo, shi) spans partitioning
        [lo, hi); one bundle file is written per span (coalesced execution
        keeps the per-task on-disk layout).  ``block=False`` skips the final
        host sync and returns device arrays (only valid without a bundler).

        ``defer_write=True`` (bundler only) dispatches the compute and
        returns a zero-arg closure that performs the host sync + bundle
        writes when called — the engine's writer thread runs it so the
        write of this bundle overlaps the dispatch of the next one
        (``stats["write_s"]`` accumulates on the closure's thread).
        """
        t0 = time.monotonic()
        n = hi - lo
        samples = np.asarray(samples)
        if len(samples) != n:
            raise ValueError(f"sample block has {len(samples)} rows "
                             f"for range [{lo}, {hi})")
        padded = bucket_for(n) if self.bucketed else n
        batch = jnp.asarray(pad_rows(samples, padded))
        # seeds beyond hi are masked work: their outputs are sliced away
        seeds = jnp.arange(lo, lo + padded, dtype=jnp.uint32)
        out = self._compiled(padded)(batch, seeds)
        if padded != n:
            out = jax.tree.map(lambda a: a[:n], out)
        self.stats["bundles"] += 1
        self.stats["samples"] += n
        self.stats["padded_samples"] += padded - n
        self.stats["launches"] += 1
        if self._mesh_divides(padded):
            self.stats["mesh_launches"] += 1
        if self.bundler is not None:
            spans = tuple(sub_ranges or ((lo, hi),))

            def finish_write(dev_out=out):
                tw = time.monotonic()
                jax.block_until_ready(dev_out)  # sync once, at write time
                host = jax.tree.map(np.asarray, dev_out)
                for slo, shi in spans:
                    sl = slice(slo - lo, shi - lo)
                    self.bundler.write_bundle(
                        slo, shi, {k: v[sl] for k, v in host.items()})
                self.stats["write_s"] += time.monotonic() - tw
                return host
            if defer_write:
                self.stats["sim_time"] += time.monotonic() - t0
                return finish_write
            out = finish_write()
        elif block:
            out = jax.tree.map(np.asarray, out)
        self.stats["sim_time"] += time.monotonic() - t0
        return out

    def step_fn(self) -> Callable:
        """A Merlin fn-step: simulate ctx's sample block and bundle results.

        Under deferred execution (the engine's write pipeline) the bundle
        write is parked on ``ctx.defer`` so it runs on the writer thread,
        after this batch's compute but overlapping the next dispatch."""
        def step(ctx):
            block = ctx.sample_block
            if block is None:
                raise ValueError("ensemble step requires study samples")
            pending = self.run_bundle(
                ctx.lo, ctx.hi, block,
                sub_ranges=getattr(ctx, "sub_ranges", None),
                defer_write=getattr(ctx, "deferrable", False))
            if callable(pending):
                ctx.defer(pending)
        return step
