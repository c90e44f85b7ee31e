"""Master benchmark harness: one entry per paper table/figure + framework
benches (roofline report, kernels, serving). Prints ``name,us_per_call,
derived`` CSV rows; detailed tables go to stdout above each row.

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [--force]
       PYTHONPATH=src python -m benchmarks.run --only qos   # QoS family

The ``qos`` entry (benchmarks/fig_qos.py, also `make bench-qos`) sweeps
3-class CPU+GPU+HWA mixes and reports per-class QoS: frame-deadline-met
rate (`dl_met_rate`), per-class p95/p99 request latency from the issue-time
latency histogram (`lat_p99_cpu`, `lat_p99_hwa`, ...), class-masked max
slowdown (`cpu_max_slowdown`, `hwa_max_slowdown`), and `squash_prio`'s
urgent-tier admission count.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def _section(title: str):
    print(f"\n{'=' * 72}\n== {title}\n{'=' * 72}")


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller workload counts / cycles")
    ap.add_argument("--force", action="store_true", help="ignore caches")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()

    n_per_cat = 4 if args.quick else 15
    n_small = 3 if args.quick else 7
    cycles = 8_000 if args.quick else 16_000
    cycles_small = 6_000 if args.quick else 12_000

    from benchmarks import (buffer_scaling, dash_deadline, fig_energy,
                            fig_pareto, fig_qos, fig1_characteristics,
                            fig4_perf_fairness, fig5_cpu_gpu,
                            fig6_core_scaling, fig7_channel_scaling,
                            p_sensitivity, power_area, simspeed)

    benches = [
        # quick mode measures at reduced scale and must not overwrite the
        # canonical BENCH_simspeed.json baseline comparison
        ("simspeed", lambda: simspeed.main(
            sweep_scale=dict(n_per_cat=2, n_cycles=2_000, warmup=500),
            policy_scale=dict(n_per_cat=2, n_cycles=1_000, warmup=200),
            write=False) if args.quick else simspeed.main()),
        ("fig1", lambda: fig1_characteristics.main(force=args.force)),
        ("fig4", lambda: fig4_perf_fairness.main(n_per_cat, cycles,
                                                 args.force)),
        ("fig5", lambda: fig5_cpu_gpu.main(n_per_cat, cycles, args.force)),
        ("fig6", lambda: fig6_core_scaling.main(n_small, cycles_small,
                                                args.force)),
        ("fig7", lambda: fig7_channel_scaling.main(n_small, cycles_small,
                                                   args.force)),
        ("p_sens", lambda: p_sensitivity.main(n_small, cycles_small,
                                              args.force)),
        ("buffer", lambda: buffer_scaling.main(n_small, cycles_small,
                                               args.force)),
        ("power", lambda: power_area.main(force=args.force)),
        ("energy", lambda: fig_energy.main(2 if args.quick else 3,
                                           cycles_small, args.force)),
        ("dash", lambda: dash_deadline.main(
            8_000 if args.quick else 12_000, args.force)),
        ("qos", lambda: fig_qos.main(3 if args.quick else 4,
                                     8_000 if args.quick else 12_000,
                                     args.force)),
        ("dse", lambda: fig_pareto.main(2 if args.quick else 3,
                                        6_000 if args.quick else 8_000,
                                        args.force)),
    ]

    # framework benches (present once their modules are built)
    try:
        from benchmarks import roofline_report
        benches.append(("roofline", roofline_report.main))
    except ImportError:
        pass
    try:
        from benchmarks import kernel_bench
        benches.append(("kernels", kernel_bench.main))
    except ImportError:
        pass
    try:
        from benchmarks import serving_bench
        benches.append(("serving", lambda: serving_bench.main(
            quick=args.quick)))
    except ImportError:
        pass

    only = set(args.only.split(",")) if args.only else None
    failed = []
    for name, fn in benches:
        if only and name not in only:
            continue
        _section(name)
        t0 = time.time()
        try:
            fn()
            print(f"[{name} done in {time.time() - t0:.0f}s]")
        except Exception as e:
            failed.append(name)
            print(f"[{name} FAILED: {type(e).__name__}: {e}]")
            traceback.print_exc()
    _section("summary")
    print(f"benchmarks: {len(benches) - len(failed)} ok, "
          f"{len(failed)} failed {failed if failed else ''}")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
