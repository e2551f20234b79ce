"""The 100M-JAG Sierra study (paper Sec. 3.1), scaled to this machine.

Reproduces every mechanism of the original at 1/10000 scale:
  * YAML study spec (simulate -> aggregate funnel),
  * hierarchical task generation from ONE enqueued message,
  * bundles of simulations fused per task, hierarchical npz bundling
    (10 sims/bundle file, 100 files/leaf -> 1000-sim aggregates),
  * injected worker failures (the "volatile early-access period"),
  * crawl-and-resubmit recovery passes: completion goes ~70% -> ~100%,
    mirroring the paper's 70% -> 85% -> 99.755% arc.

The study itself is a declarative spec file (examples/specs/
icf_ensemble.yaml) compiled into the runtime's task DAG — the code below
only registers the two fn-steps and drives the run.

Run: PYTHONPATH=src python examples/icf_ensemble.py [n_samples]
"""
import os
import sys
import tempfile
import time

import jax
import numpy as np

from repro.core import Bundler, EnsembleExecutor, MerlinRuntime, StudySpec, WorkerPool
from repro.core.hierarchy import HierarchyCfg
from repro.core.resilience import crawl_and_resubmit
from repro.sim import jag_simulate, jag_sample_inputs

SPEC_PATH = os.path.join(os.path.dirname(__file__), "specs",
                         "icf_ensemble.yaml")


def main(n_samples: int = 10_000):
    with tempfile.TemporaryDirectory() as ws:
        rt = MerlinRuntime(workspace=ws,
                           hierarchy=HierarchyCfg(max_fanout=16, bundle=10))
        bundler = Bundler(f"{ws}/jag", files_per_leaf=100)
        executor = EnsembleExecutor(jag_simulate, bundler)
        rt.register("simulate", executor.step_fn())
        agg_stats = {}

        def aggregate(ctx):
            outs = bundler.aggregate_all()
            agg_stats["n_aggregates"] = len(outs)
        rt.register("aggregate", aggregate)

        with open(SPEC_PATH) as f:
            spec = StudySpec.from_yaml(f.read())
        samples = np.asarray(jag_sample_inputs(jax.random.PRNGKey(0),
                                               n_samples))

        rt.broker._vt = 1.0  # fast redelivery for dead workers
        t0 = time.time()
        # 30% worker death rate: the "volatile early access period"
        with WorkerPool(rt, n_workers=4, failure_rate=0.3, seed=3) as pool:
            study = rt.run(spec, samples)
            rt.wait(study, timeout=600)
            pool.drain(timeout=60)
            present, corrupt = bundler.crawl()
            print(f"pass 1: {len(present)}/{n_samples} "
                  f"({100 * len(present) / n_samples:.1f}%) complete, "
                  f"{rt.broker.stats['redelivered']} redeliveries, "
                  f"{time.time() - t0:.1f}s")

            # recovery passes: crawl the tree, resubmit missing work
            tmpl = {"study": study, "stage": 0, "combo": 0,
                    "n_samples": n_samples, "fanout": 16, "bundle": 10}
            for p in range(2, 6):
                missing, ntasks = crawl_and_resubmit(
                    bundler, n_samples, rt.broker, tmpl, bundle=10)
                if missing == 0:
                    break
                pool.drain(timeout=120)
                present, _ = bundler.crawl()
                print(f"pass {p}: resubmitted {ntasks} tasks -> "
                      f"{len(present)}/{n_samples} "
                      f"({100 * len(present) / n_samples:.2f}%)")

        rate = executor.stats["samples"] / (time.time() - t0)
        data = bundler.load_all()
        ok = np.isfinite(data["yield"])
        print(f"final: {len(present)}/{n_samples} on disk; "
              f"{int((~ok).sum())} internal physics failures "
              f"({100 * (~ok).mean():.2f}%, cf. paper's 0.22%)")
        print(f"dataset: {data['images'].nbytes / 2**20:.0f} MiB of images, "
              f"{agg_stats.get('n_aggregates', 0)} aggregate files, "
              f"{rate:.0f} sims/s simulated and bundled (wall clock)")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 10_000)
