"""Quickstart: the whole Merlin-on-JAX story in ~60 lines.

1. Define a study (simulate -> collect) over 512 JAG ICF samples.
2. Run it through the producer-consumer runtime with 4 surge-able workers
   (one root message enqueued; workers expand the task hierarchy).
3. Train an ML surrogate on the bundled ensemble and report its fit —
   the "ML-ready" part of ML-ready HPC ensembles.

Run: PYTHONPATH=src python examples/quickstart.py

Two-process mode (the paper's actual deployment shape — a standalone
broker host, like the RabbitMQ server of Sec. 2-3):

    PYTHONPATH=src python examples/quickstart.py --two-process

spawns ``python -m repro.launch.serve broker-serve`` as a separate OS
process and attaches the runtime + worker pool to it over TCP.  The queue
lives entirely in the server process — no shared directory, no shared
memory; kill either side and the other's leases expire and redeliver.

Sharded mode (the federation that scales past one broker process):

    PYTHONPATH=src python examples/quickstart.py --sharded

spawns TWO broker-serve processes (shards 0/2 and 1/2) and connects the
runtime with ``broker=[url0, url1]`` — a ShardedBroker that routes each
queue to its owning shard by stable hash.  The study's ``real`` and
``gen`` queues land on different shards, so generation and simulation
traffic never share a broker process.
"""
import argparse
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from repro.core import Bundler, EnsembleExecutor, MerlinRuntime, Step, StudySpec, WorkerPool
from repro.core import spans
from repro.core.active import train_surrogate
from repro.core.hierarchy import HierarchyCfg
from repro.data.pipeline import regression_dataset
from repro.sim import jag_simulate, jag_sample_inputs
import jax


def spawn_broker_server(workspace: str, name: str = "broker",
                        extra_args: "list[str]" = ()) \
        -> "tuple[subprocess.Popen, str]":
    """Start a broker-serve process; return (proc, tcp:// URL)."""
    port_file = os.path.join(workspace, f"{name}.port")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "broker-serve",
         "--port", "0", "--port-file", port_file, *extra_args],
        env={**os.environ,
             "PYTHONPATH": "src" + os.pathsep + os.environ.get("PYTHONPATH", "")})
    deadline = time.monotonic() + 30
    while not os.path.exists(port_file):
        if proc.poll() is not None:
            raise RuntimeError("broker server died during startup")
        if time.monotonic() > deadline:
            proc.terminate()
            raise RuntimeError("broker server did not come up in 30s")
        time.sleep(0.05)
    with open(port_file) as f:
        port = int(f.read())
    return proc, f"tcp://127.0.0.1:{port}"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--two-process", action="store_true",
                    help="host the queue in a separate broker-serve process "
                         "(no shared filesystem for the queue)")
    ap.add_argument("--sharded", action="store_true",
                    help="host the queues on TWO broker-serve shard "
                         "processes, federated client-side by queue hash")
    args = ap.parse_args(argv)

    procs = []
    with tempfile.TemporaryDirectory() as ws:
        broker = None  # default: in-process InMemoryBroker
        if args.sharded:
            urls = []
            for i in range(2):
                p, url = spawn_broker_server(
                    ws, name=f"shard{i}", extra_args=["--shard-of", f"{i}/2"])
                procs.append(p)
                urls.append(url)
                print(f"shard {i}/2 up at {url} (pid {p.pid})")
            broker = urls  # a list of endpoints == a ShardedBroker
        elif args.two_process:
            proc, broker = spawn_broker_server(ws)
            procs.append(proc)
            print(f"broker server up at {broker} (pid {proc.pid})")
        try:
            # 1. runtime + study ---------------------------------------------
            rt = MerlinRuntime(broker=broker, workspace=ws,
                               hierarchy=HierarchyCfg(max_fanout=8, bundle=64))
            if args.sharded:
                sb = rt.broker  # the ShardedBroker built from the URL list
                print("queue routing: " + ", ".join(
                    f"{q} -> shard {sb.shard_for(q)}"
                    for q in (rt.real_queue, rt.gen_queue)))
            bundler = Bundler(f"{ws}/results", files_per_leaf=4)
            executor = EnsembleExecutor(jag_simulate, bundler)
            rt.register("simulate", executor.step_fn())
            spec = StudySpec(name="quickstart", steps=[
                Step(name="simulate", fn="simulate")])

            samples = np.asarray(jag_sample_inputs(jax.random.PRNGKey(0), 512))

            # 2. producer-consumer execution ---------------------------------
            t_run = time.perf_counter()
            with WorkerPool(rt, n_workers=4) as pool:
                study = rt.run(spec, samples)      # `merlin run`: one message
                assert rt.wait(study, timeout=120)
                t = spans.totals()
                print(f"workers processed {pool.stats()['real']} bundles "
                      f"({executor.stats['samples']} simulations in "
                      f"{time.perf_counter() - t_run:.2f}s; launches "
                      f"{t['executor.launch']['s']:.2f}s, host sync "
                      f"{t['executor.host_sync']['s']:.2f}s, bundle writes "
                      f"{t['bundler.write']['s']:.2f}s)")

            # 3. ML-ready: train a surrogate on the ensemble -----------------
            data = bundler.load_all()
            X, y = regression_dataset(data, target="yield")
            n = len(X)
            sur = train_surrogate(X[: n // 2], y[: n // 2], steps=400)
            mu, sd = sur.predict(X[n // 2:])
            ss_res = float(np.mean((mu - y[n // 2:]) ** 2))
            ss_tot = float(np.var(y[n // 2:]))
            print(f"surrogate R^2 on held-out half: {1 - ss_res / ss_tot:.3f} "
                  f"(n_train={n // 2})")
        finally:
            for proc in procs:
                proc.terminate()
                proc.wait(timeout=10)


if __name__ == "__main__":
    main()
