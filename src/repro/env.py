"""Process-level runtime environment, applied once at entry.

Every perf-sensitive entrypoint (``benchmarks/run.py``, ``broker-serve``,
the drain/worker subprocess mains) used to inherit whatever environment
it was launched with: JAX picking its own x64/platform defaults, BLAS
and XLA each spawning their idea of a thread pool, allocator choice
unrecorded.  On an HPC node that's both a throughput problem (thread
oversubscription on shared cores) and a reproducibility problem — two
"identical" benchmark runs on differently-tuned shells are not
comparable, and nothing in the artifact said so.

:func:`configure` is the one place this is decided.  It is intentionally
boring: read ``REPRO_*`` environment overrides, apply deterministic
defaults, record everything it did, and never do it twice.  The returned
snapshot is embedded in ``BENCH_*.json`` meta so every committed number
carries the environment that produced it.

Knobs (call argument > ``REPRO_*`` env var > default):

========================  =======================  =========================
argument                  env var                  effect
========================  =======================  =========================
``x64``                   ``REPRO_X64``            ``JAX_ENABLE_X64`` (or
                                                   ``jax.config`` when jax
                                                   is already imported)
``platform``              ``REPRO_PLATFORM``       ``JAX_PLATFORMS``
``host_device_count``     ``REPRO_HOST_DEVICES``   ``--xla_force_host_``
                                                   ``platform_device_count``
                                                   in ``XLA_FLAGS``
``threads``               ``REPRO_THREADS``        OMP/OpenBLAS/MKL/numexpr
                                                   thread counts (default:
                                                   physical ``cpu_count``)
``extra_xla_flags``       ``REPRO_XLA_FLAGS``      appended to ``XLA_FLAGS``
``debug_nans``            ``REPRO_DEBUG_NANS``     ``JAX_DEBUG_NANS``
========================  =======================  =========================

Persistent compilation cache: an exported ``JAX_COMPILATION_CACHE_DIR``
is used as is.  Otherwise the cache lives at the fixed in-checkout path
``<repo>/.jax_cache`` (derived from this file's location, never from a
temp name, pid or time: the path is part of the cache key, so a moving
directory would never hit).  The variable is exported so child processes
share the same cache; when jax is already imported the directory is also
applied through ``jax.config``.

Thread pinning uses ``setdefault``: an operator who already exported
``OMP_NUM_THREADS=4`` wins over our default, but an unpinned shell gets
a deterministic count instead of library roulette.  XLA/JAX env flags
only take effect when set *before* ``import jax`` — when jax is already
imported, :func:`configure` falls back to ``jax.config.update`` for the
knobs that support it and records ``"jax_preimported": true`` so a
late application is visible in the artifact rather than silently
ineffective.  tcmalloc is detect-only (we never dlopen): if the
launcher preloaded it (the classic ``LD_PRELOAD=libtcmalloc.so.4``
HPC idiom), the snapshot says so and the large-alloc report threshold
is defaulted to keep it quiet.

This module must stay importable without jax — ``broker-serve`` and the
drain workers are jax-free processes.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Dict, Optional

_THREAD_VARS = ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS",
                "MKL_NUM_THREADS", "NUMEXPR_NUM_THREADS")
_DEVICE_FLAG = "--xla_force_host_platform_device_count"
_CACHE_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

_applied: Optional[Dict[str, Any]] = None


def _env_bool(name: str) -> Optional[bool]:
    v = os.environ.get(name)
    if v is None or v == "":
        return None
    return v.strip().lower() not in ("0", "false", "no", "off")


def _env_int(name: str) -> Optional[int]:
    v = os.environ.get(name)
    if v is None or v == "":
        return None
    try:
        return int(v)
    except ValueError:
        return None


def _tcmalloc_loaded() -> bool:
    if "tcmalloc" in os.environ.get("LD_PRELOAD", ""):
        return True
    try:
        with open("/proc/self/maps") as f:
            return any("tcmalloc" in line for line in f)
    except OSError:
        return False


def configure(x64: Optional[bool] = None, platform: Optional[str] = None,
              host_device_count: Optional[int] = None,
              threads: Optional[int] = None,
              extra_xla_flags: Optional[str] = None,
              debug_nans: Optional[bool] = None) -> Dict[str, Any]:
    """Apply the runtime environment once; return the recorded snapshot.

    Idempotent: the second and later calls in a process return the
    first call's snapshot unchanged (entrypoints can all call it without
    coordinating about who runs first).
    """
    global _applied
    if _applied is not None:
        return dict(_applied)

    jax_preimported = "jax" in sys.modules
    if x64 is None:
        x64 = _env_bool("REPRO_X64")
    if platform is None:
        platform = os.environ.get("REPRO_PLATFORM") or None
    if host_device_count is None:
        host_device_count = _env_int("REPRO_HOST_DEVICES")
    if threads is None:
        threads = _env_int("REPRO_THREADS")
    if threads is None:
        threads = os.cpu_count() or 1
    if extra_xla_flags is None:
        extra_xla_flags = os.environ.get("REPRO_XLA_FLAGS") or None
    if debug_nans is None:
        debug_nans = _env_bool("REPRO_DEBUG_NANS")

    # deterministic thread pinning: an explicit operator export wins,
    # an unpinned shell gets one recorded count everywhere
    pinned: Dict[str, str] = {}
    for var in _THREAD_VARS:
        os.environ.setdefault(var, str(threads))
        pinned[var] = os.environ[var]

    xla_parts = [f for f in os.environ.get("XLA_FLAGS", "").split() if f]
    if host_device_count is not None and not jax_preimported \
            and not any(p.startswith(_DEVICE_FLAG) for p in xla_parts):
        xla_parts.append(f"{_DEVICE_FLAG}={int(host_device_count)}")
    if extra_xla_flags and not jax_preimported:
        xla_parts.extend(f for f in extra_xla_flags.split()
                         if f not in xla_parts)
    if xla_parts:
        os.environ["XLA_FLAGS"] = " ".join(xla_parts)

    cache_dir = os.environ.get(_CACHE_VAR) or None
    if cache_dir is None:
        cache_dir = os.environ[_CACHE_VAR] = DEFAULT_CACHE_DIR
        if jax_preimported:
            import jax
            jax.config.update("jax_compilation_cache_dir", cache_dir)

    if not jax_preimported:
        if x64 is not None:
            os.environ["JAX_ENABLE_X64"] = "1" if x64 else "0"
        if platform:
            os.environ["JAX_PLATFORMS"] = platform
        if debug_nans is not None:
            os.environ["JAX_DEBUG_NANS"] = "1" if debug_nans else "0"
    else:
        # too late for env/XLA flags; apply what jax.config still honors
        import jax
        if x64 is not None:
            jax.config.update("jax_enable_x64", bool(x64))
        if debug_nans is not None:
            jax.config.update("jax_debug_nans", bool(debug_nans))

    tcmalloc = _tcmalloc_loaded()
    if tcmalloc:
        # silence per-allocation report spam on big arrays (128 GiB bar)
        os.environ.setdefault("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD",
                              str(128 << 30))

    _applied = {
        "x64": x64,
        "platform": platform,
        "host_device_count": host_device_count,
        "threads": int(threads),
        "thread_pins": pinned,
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "debug_nans": debug_nans,
        "tcmalloc": tcmalloc,
        "jax_preimported": jax_preimported,
        "compilation_cache_dir": cache_dir,
    }
    return dict(_applied)


def snapshot() -> Dict[str, Any]:
    """The applied environment (configuring with defaults on first use),
    for embedding in benchmark artifacts."""
    return configure()


def _reset_for_tests() -> None:
    global _applied
    _applied = None
