"""Benchmark orchestrator: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (harness contract) and writes
the simulator-scaling rows to ``BENCH_sim.json`` (machine-readable, suitable
for CI artifact upload -- see .github/workflows/ci.yml).

``--smoke`` runs a minutes-scale subset (used by the CI benchmark job);
the default budgets match the curves in EXPERIMENTS.md.  Each bench_*
module also has a __main__ with --rounds/--out for full sweeps.

Every benchmark runs through :func:`_step`, which prints the per-benchmark
wall time to stderr and, on failure, exits naming the failing benchmark --
so a red bench-smoke CI lane is diagnosable from the last log line instead
of a bare traceback.

Everything that needs the device runs in this one process (a chip belongs
to one process at a time).  The only children are the mesh-scaling
study's, which run on the CPU by design (bench_sharded_scaling).  On the
CPU (``JAX_PLATFORMS=cpu``) the process exposes a host mesh of 4 (smoke)
or 8 virtual devices for the 100M frontier.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


def _step(name: str, fn, *args, **kwargs):
    """Run one benchmark, print its wall time, exit naming it on failure."""
    t0 = time.perf_counter()
    try:
        out = fn(*args, **kwargs)
    except BaseException as e:
        if isinstance(e, (KeyboardInterrupt, SystemExit)):
            raise
        traceback.print_exc()
        print(f"[bench] FAILED {name} after {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        sys.exit(f"benchmark failed: {name}")
    print(f"[bench] {name}: {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced budgets + small device counts (CI)")
    ap.add_argument("--sim-json", default="BENCH_sim.json",
                    help="path for the machine-readable scaling rows")
    ap.add_argument("--controller-json", default="BENCH_controller.json",
                    help="path for the controller fleet-vs-list rows")
    ap.add_argument("--sharded-json", default="BENCH_sharded.json",
                    help="path for the mesh-scaling rows (sharded engine)")
    ap.add_argument("--scenarios-json", default="BENCH_scenarios.json",
                    help="path for the scenario-zoo fixed-vs-DDPG rows")
    ap.add_argument("--tasks-json", default="BENCH_tasks.json",
                    help="path for the task-zoo throughput/accuracy rows")
    ap.add_argument("--population-json", default="BENCH_population.json",
                    help="path for the population EF-store rows")
    ap.add_argument("--async-json", default="BENCH_async.json",
                    help="path for the server-aggregator wall/accuracy rows")
    ap.add_argument("--hundredm-json", default="BENCH_100m.json",
                    help="path for the 100M-stack wire/throughput frontier")
    args = ap.parse_args()

    from repro.launch.compat import (enable_compile_cache,
                                     force_host_device_count)
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        # the 100M frontier's FL devices, as a CPU host mesh
        force_host_device_count(4 if args.smoke else 8)
    enable_compile_cache()

    from benchmarks import (bench_100m, bench_async,
                            bench_compressor_throughput,
                            bench_controller_scaling,
                            bench_convergence_bound, bench_fig3_lr_mnist,
                            bench_fig5_drl, bench_fig6_rnn_shakespeare,
                            bench_population, bench_scenarios,
                            bench_sharded_scaling, bench_sim_scaling,
                            bench_table1_channels, bench_tasks)

    _step("table1_channels", bench_table1_channels.run)          # Table 1
    _step("convergence_bound", bench_convergence_bound.run)      # Thm 1
    _step("compressor_throughput", bench_compressor_throughput.run,
          sizes=(65_536,))                                       # kernels
    if args.smoke:
        sim = _step("sim_scaling", bench_sim_scaling.run,
                    ms=(8, 16), rounds=24)                       # scaling
        ctrl = _step("controller_scaling", bench_controller_scaling.run,
                     ms=(8, 64))                                 # fleet DDPG
        sharded = _step("sharded_scaling", bench_sharded_scaling.run,
                        device_counts=(1, 8), m=256, rounds=24,
                        k_windows=15)                            # mesh scaling
        scen = _step("scenarios", bench_scenarios.run,
                     m=8, rounds=30, n_train=1500)               # scenario zoo
        tasks = _step("tasks", bench_tasks.run, m=8, rounds=24)  # task zoo
        popn = _step("population", bench_population.run,
                     n_devices=100_000, m_cohort=64, rounds=24)  # EF stores
        asynch = _step("async", bench_async.run,
                       m=8, rounds=60, n_train=1500)             # aggregators
        hundredm = _step("lgc_100m", bench_100m.run,
                         preset="smoke", rounds=6)               # 100M stack
        _step("fig3_lr_mnist", bench_fig3_lr_mnist.run,
              model="lr", rounds=40, n_train=1200)
    else:
        sim = _step("sim_scaling", bench_sim_scaling.run,
                    ms=(8, 64, 256), rounds=200)
        ctrl = _step("controller_scaling", bench_controller_scaling.run,
                     ms=(8, 64, 256))
        sharded = _step("sharded_scaling", bench_sharded_scaling.run,
                        device_counts=(1, 2, 4, 8), m=256, rounds=40)
        scen = _step("scenarios", bench_scenarios.run,
                     m=16, rounds=120, n_train=4000)
        tasks = _step("tasks", bench_tasks.run, m=16, rounds=80)
        popn = _step("population", bench_population.run,
                     n_devices=100_000, m_cohort=64, rounds=80)
        asynch = _step("async", bench_async.run,
                       m=16, rounds=120, n_train=2000)
        hundredm = _step("lgc_100m", bench_100m.run,
                         preset="smoke", rounds=12)
        _step("fig3_lr_mnist", bench_fig3_lr_mnist.run,
              model="lr", rounds=100, n_train=2000)              # Fig 3
        _step("fig4_cnn_mnist", bench_fig3_lr_mnist.run,
              model="cnn", rounds=40, n_train=1500)              # Fig 4
        _step("fig5_drl", bench_fig5_drl.run, rounds=120)        # Fig 5
        _step("fig6_rnn_shakespeare", bench_fig6_rnn_shakespeare.run,
              rounds=30)                                         # Fig 6

    with open(args.sim_json, "w") as f:
        json.dump(sim, f, indent=1)
    with open(args.controller_json, "w") as f:
        json.dump(ctrl, f, indent=1)
    with open(args.sharded_json, "w") as f:
        json.dump(sharded, f, indent=1)
    with open(args.scenarios_json, "w") as f:
        json.dump(scen, f, indent=1)
    with open(args.tasks_json, "w") as f:
        json.dump(tasks, f, indent=1)
    with open(args.population_json, "w") as f:
        json.dump(popn, f, indent=1)
    with open(args.async_json, "w") as f:
        json.dump(asynch, f, indent=1)
    with open(args.hundredm_json, "w") as f:
        json.dump(hundredm, f, indent=1)


if __name__ == '__main__':
    main()
