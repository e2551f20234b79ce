"""Benchmark driver: one harness per paper figure (Sec. 2.3) plus the
device-fusion benchmark from the TPU adaptation.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = the per-unit
latency each figure is about), then a human-readable block.  Paper-claim
comparisons live in EXPERIMENTS.md.

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [--check-schema]

``--check-schema`` validates the BENCH_*.json artifacts (after --quick
refreshes them, or standalone against the committed ones) and exits
non-zero on a malformed document — CI's fence against perf-trajectory rot.
"""
import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--check-schema", action="store_true",
                    help="validate BENCH_*.json against the documented "
                         "schemas (benchmarks/README.md); exit 1 on errors")
    args = ap.parse_args()

    from repro import env as repro_env
    repro_env.configure()  # every figure runs on tuned, recorded defaults

    from benchmarks import figures as F

    rows = []

    # Fig. 3: enqueue/expansion throughput
    sizes = (100, 1000, 10_000, 100_000) if args.quick else \
        (100, 1000, 10_000, 100_000, 1_000_000)
    enq = F.bench_enqueue(sizes=sizes)
    for r in enq:
        rows.append((f"fig3_enqueue_n{r['n_samples']}",
                     1e6 / max(r["samples_per_s"], 1e-9),
                     f"{r['samples_per_s']:.0f} samples/s; merlin_run="
                     f"{r['merlin_run_s']*1e6:.0f}us"))

    # Fig. 4: startup latency vs workers
    for r in F.bench_startup(n_samples=200 if args.quick else 1000):
        rows.append((f"fig4_startup_w{r['workers']}",
                     r["startup_s"] * 1e6,
                     f"first sim after {r['startup_s']*1e3:.1f} ms"))

    # Fig. 5: per-task overhead
    o = F.bench_overhead(n_samples=500 if args.quick else 2000)
    rows.append(("fig5_overhead_per_task", o["overhead_per_task_s"] * 1e6,
                 f"median_work={o['median_task_s']*1e3:.2f}ms "
                 f"wall={o['wall_s']:.2f}s"))

    # Fig. 6: worker scaling
    for r in F.bench_scaling(n_samples=64 if args.quick else 256):
        rows.append((f"fig6_scaling_w{r['workers']}",
                     r["wall_s"] * 1e6 / 256,
                     f"efficiency={r['efficiency']:.2f} vs ideal"))

    # TPU adaptation: fused-bundle per-sample overhead
    for r in F.bench_fused(bundle_sizes=(1, 16, 256) if args.quick
                           else (1, 16, 256, 1024)):
        rows.append((f"fused_bundle_{r['bundle']}",
                     r["us_per_sample"],
                     f"{r['samples_per_s']:.0f} sims/s"))

    # ensemble hot-path bench: in --quick mode run it tiny and emit the
    # BENCH_ensemble.json perf-trajectory artifact at the repo root
    if args.quick:
        from benchmarks import ensemble_throughput as ET
        et = ET.run(quick=True)
        for scen in ("ragged", "uniform"):
            rows.append((f"ensemble_{scen}",
                         1e6 / et[scen]["fused"]["samples_per_s"],
                         f"{et[scen]['speedup']:.1f}x vs per-task path; "
                         f"{et[scen]['fused']['traces']} compiles "
                         f"(bound {et[scen]['bucket_bound']})"))
        rows.append(("ensemble_surrogate_train",
                     et["surrogate"]["scanned_s"] * 1e6,
                     f"{et['surrogate']['speedup']:.1f}x vs eager loop"))
        xb = et["engine_xbatch"]
        rows.append(("ensemble_engine_xbatch",
                     1e6 / xb["xbatch"]["samples_per_s"],
                     f"{xb['speedup']:.2f}x vs per-worker coalescing "
                     f"(bar >= 2x); launches "
                     f"{xb['per_worker']['launches']} -> "
                     f"{xb['xbatch']['launches']}"))
        md = et.get("mesh_dispatch", {})
        if md:
            rows.append(("ensemble_mesh_dispatch",
                         1e6 / md["jag_sharded"]["samples_per_s"],
                         f"{md['devices']} forced host devices; "
                         f"bit_equal={md['bit_equal']}, jag rel diff "
                         f"{md['jag_max_rel_diff']:.1e}"))
        # broker bench (tiny): refreshes BENCH_broker.json so the perf
        # trajectory covers the federated (sharded) topology too
        from benchmarks import broker_throughput as BT
        bt = BT.run(quick=True)
        shard = bt["scenarios"]["shard2_mem_procs4_b8"]
        rows.append(("broker_shard2_mem_procs4_b8",
                     1e6 / shard["tasks_per_s"],
                     f"{bt['acceptance']['shard2_vs_net_mem_b8']:.2f}x vs "
                     f"one server, same consumer fleet (bar >= "
                     f"{bt['acceptance']['shard_bar']}x)"))
        rows.append(("broker_bin1_vs_json_arr_b32",
                     1e6 / bt["scenarios"][
                         "net_mem_arr_w1_b32_bin1"]["tasks_per_s"],
                     f"{bt['acceptance']['bin1_vs_json_arr_b32']:.2f}x vs "
                     f"JSON on array payloads (bar >= 3x)"))
        rows.append(("broker_shm_w4_b8",
                     1e6 / bt["scenarios"]["shm_w4_b8"]["tasks_per_s"],
                     f"{bt['acceptance']['shm_vs_net_mem_procs4_b8']:.2f}x "
                     f"vs tcp, same-host fleet (bar > 1x)"))
        el = bt["scenarios"]["elastic_rebalance"]
        rows.append(("broker_elastic_rebalance",
                     1e6 / el["tasks_per_s"],
                     f"rebalance {el['rebalance_s']:.2f}s; moved "
                     f"{bt['acceptance']['elastic_moved_fraction']:.2f} of "
                     f"queues (bar <= "
                     f"{bt['acceptance']['elastic_moved_bar']:.2f}); "
                     f"loss={el['task_loss']}"))
        # serving-gateway bench (small fleet): refreshes BENCH_serve.json
        # so the perf trajectory covers the inference tier too
        from benchmarks import serve_latency as SL
        sl = SL.run(quick=True)
        sa = sl["acceptance"]
        cont = sl["scenarios"]["continuous"]
        rows.append(("serve_continuous",
                     1e6 / max(cont["requests_per_s"], 1e-9),
                     f"{sa['continuous_vs_naive_rps']:.2f}x vs "
                     f"flush-per-request (bar >= 2x); p99 "
                     f"{sa['continuous_p99_ms']:.0f}ms vs "
                     f"{sa['naive_p99_ms']:.0f}ms"))
        over = sl["scenarios"]["overload_shed"]
        rows.append(("serve_overload_shed",
                     1e6 / max(over["requests_per_s"], 1e-9),
                     f"shed_rate={sa['shed_rate']:.2f} (bar > 0); "
                     f"accounting_ok={sa['accounting_ok']}"))

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")

    # roofline table if dry-run results exist
    try:
        from benchmarks import roofline
        print()
        roofline.main()
    except Exception as e:  # pragma: no cover
        print(f"(roofline table skipped: {e})", file=sys.stderr)

    if args.check_schema:
        from benchmarks.bench_schema import check_all
        errs = check_all()
        for e in errs:
            print(f"schema error: {e}", file=sys.stderr)
        if errs:
            sys.exit(1)
        print("BENCH_*.json schemas OK")


if __name__ == "__main__":
    main()
