"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``chipbench/workloads/<cell>.json``; it names a configuration
(``chipbench/configs/<config>.json``) and a driver kind
(``chipbench/drivers/<kind>.py``).  Which metrics the cell reports comes
from ``BENCHMARK.json`` at the root of the checkout, and each metric is
read by ``chipbench/metrics/<metric>.py``.  Adding a cell, a configuration,
a driver kind or a metric therefore adds files and entries, and edits no
code here.

A run sets up (process start, program import, device start, inputs from
the seed, warm-up of every shape the cell uses), measures for ``--seconds``
with nothing compiling, then checks what the timed path produced against
the plain reference in ``chipbench/reference/<config>.py``.  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``),
and last ``checks``, each compared number beside its limit.  The same
numbers are the last lines of standard error.

The run fails, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  It never falls back to the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from process start

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# run-time files (bundles, traces) live inside the checkout, at a path
# that .gitignore lists; every run removes its own directory at exit
WORK_DIR = os.path.join(ROOT, ".chipbench_work")
# JAX's persistent compilation cache: a fixed path inside the checkout,
# so only the first run of a cell in a checkout compiles
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
with open(os.path.join(BENCH_DIR, "peaks.json")) as _f:
    PEAKS = json.load(_f)  # published peaks of one chip, by device_kind


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """Import ``chipbench/<kind>/<name>.py`` by path (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, group: str) -> list:
    """The entries of ``bench[group]`` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


class Run:
    """What a driver gets: the cell, its configuration, the seed, the
    window length, a work directory, and the hooks that time set-up and
    take the profiler trace."""

    def __init__(self, cell: dict, config: dict, seed: int, seconds: float,
                 trace: bool, workdir: str, devices: list):
        self.cell = cell
        self.config = config
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.workdir = workdir
        self.devices = devices
        self.reference = load_module("reference", config["name"])
        self.setup_s = None
        self.trace_summary = None
        self.memory_peak_bytes = None
        self._trace_t0 = None

    def simulator(self):
        """The configuration's simulator entry, ``module:function``, with
        the size constants of ``module_sizes`` set on their modules before
        anything traces it.  A constant the module lacks is an error."""
        for module, sizes in self.config.get("module_sizes", {}).items():
            mod = importlib.import_module(module)
            for key, value in sizes.items():
                if not hasattr(mod, key):
                    raise AttributeError(f"{module} has no size {key!r}")
                setattr(mod, key, value)
        module, name = self.config["simulator"].split(":")
        return getattr(importlib.import_module(module), name)

    def window_opens(self) -> float:
        """Called by the driver the moment set-up ends; returns the clock."""
        now = time.perf_counter()
        self.setup_s = now - T_START
        return now

    def trace_start(self) -> None:
        import jax
        self._trace_dir = os.path.join(self.workdir, "trace")
        jax.profiler.start_trace(self._trace_dir)
        self._trace_t0 = time.perf_counter()

    def trace_stop(self) -> None:
        import jax
        window_s = time.perf_counter() - self._trace_t0
        jax.profiler.stop_trace()
        self._trace_window_s = window_s

    def reduce_trace(self) -> None:
        """Reduce the trace taken between trace_start and trace_stop."""
        if self._trace_t0 is None:
            return
        from chipbench import trace as T
        path = T.find_xplane(self._trace_dir)
        events = T.device_events(path, len(self.devices))
        self.trace_summary = T.reduce(events, self._trace_window_s)

    def read_memory_peak(self) -> None:
        """Peak bytes on the fullest chip; read before the reference runs."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        self.memory_peak_bytes = int(max(peaks))


def check_devices(chips: int, allow_cpu: bool):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    if devs[0].platform == "tpu" and devs[0].device_kind not in PEAKS:
        raise RuntimeError(f"{devs[0].device_kind!r} is not in peaks.json")
    return devs[:chips]


def configure_jax() -> None:
    """Compile cache and platforms, before JAX starts a backend."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        # the reference runs on the host CPU device next to the chip
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    from repro import env as repro_env
    repro_env.configure()
    import jax
    # cache every program, however quick its compile, so that a warm
    # run compiles nothing at all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def result_line(run: Run, readings: dict, bench: dict, cell_name: str,
                trace: bool) -> dict:
    group = "per_layer" if trace else "end_to_end"
    readings = dict(readings, setup_s=run.setup_s, trace=run.trace_summary)
    metrics = {}
    for m in cell_metrics(bench, cell_name, group):
        value = load_module("metrics", m["name"]).read(readings)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": readings["correct"], "attempted": readings["attempted"],
           "failed": readings["failed"], "metrics": metrics, "device": device}
    if trace and run.trace_summary is not None:
        s = run.trace_summary
        device.update(busy_s=s["busy_s"], window_s=s["window_s"])
        out["breakdown"] = {"device_ops": s["device_ops"],
                            "idle_gaps": s["idle_gaps"]}
    out["checks"] = readings["checks"]
    return out


def main(argv=None, allow_cpu: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = load_json(BENCH_DIR, "workloads", args.workload + ".json")
    config = load_json(BENCH_DIR, "configs", cell["config"] + ".json")
    driver = load_module("drivers", cell["driver"])

    configure_jax()
    devices = check_devices(int(cell["chips"]), allow_cpu)
    os.makedirs(WORK_DIR, exist_ok=True)
    workdir = tempfile.mkdtemp(prefix=args.workload + "-", dir=WORK_DIR)
    try:
        run = Run(cell, config, args.seed, args.seconds, bool(args.trace),
                  workdir, devices)
        readings = driver.run(run)
        if devices[0].platform == "tpu":  # a CPU rehearsal has no device plane
            run.reduce_trace()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    line = result_line(run, readings, bench, args.workload, bool(args.trace))
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        sys.exit(3)
