"""Benchmark driver: one section per paper table/figure + framework extras.

    PYTHONPATH=src python -m benchmarks.run [--scale 0.3] [--json out.json]

Sections:
  fig4   degree distributions of the evaluation graphs
  fig6   partition methods: time + quality (the paper's headline table)
  table2 EP-SpMV vs default: modeled loads + partition overhead + allclose
  fig11  normalized transaction counts
  fig12  software vs streaming (texture) cache
  table3 block-size sensitivity
  fig13  general workloads + MoE dispatch + adaptive control (fig14)
  hier   beyond-paper two-level EP (ICI + HBM)
  svc    PartitionService: cold vs warm-cache vs incremental repartition
  svc_streaming  long-lived per-tenant churn streams sweeping the 1-20%
         band: drift-gated gear mix (incremental/local/full), p50/p99
         update latency, quality drift vs same-run full rebuilds
  svc_multitenant  tenant-budget isolation under cache flood + worker-pool
         cold-plan throughput (1 worker vs machine-sized process pool)
  svc_batched  bucketed kernel compilation + micro-batched serving vs
         per-shape dedicated compiles (many-small-graphs scenario)
  svc_chaos  replicated plan service under fault injection: kill-a-replica
         failover (zero lost tickets, byte-identical responses) + hedging
         vs an injected straggler
  perf   per-stage partition->pack timings (coarsen/init/refine/pack)
  roofline  dry-run roofline table (if artifacts exist)

``--only`` accepts a comma-separated list (e.g. ``--only fig6,svc,perf``).

``--json PATH`` writes every section's structured rows (plus timings and the
scale) so CI can track the BENCH_* perf trajectory per PR and
``scripts/check_bench_regression.py`` can diff against the baseline.

``--profile`` wraps the selected sections in cProfile and prints the top 20
functions by cumulative time — so when a stage table shows a new dominant
cost, finding the function behind it is one flag away, no editing required.
"""
from __future__ import annotations

import argparse
import json
import time


def _jsonable(obj):
    """Best-effort conversion of section results (numpy scalars etc.)."""
    import numpy as np

    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    return str(obj)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.3,
                    help="graph size multiplier for the partitioning benches")
    ap.add_argument("--only", default=None,
                    help="run selected sections (comma-separated)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write section results + timings as JSON")
    ap.add_argument("--profile", action="store_true",
                    help="run the selected sections under cProfile and "
                         "print the top 20 functions by cumulative time")
    args = ap.parse_args(argv)

    from repro.kernels.backend import use_compile_cache

    use_compile_cache()
    from . import (
        fig4_degree_dist,
        fig6_partition_methods,
        fig11_transactions,
        fig12_cache_types,
        fig13_apps,
        hierarchy_bench,
        perf_stages,
        roofline,
        svc_batched,
        svc_chaos,
        svc_multitenant,
        svc_service,
        svc_streaming,
        table2_spmv,
        table3_block_size,
    )

    sections = {
        "fig4": lambda: fig4_degree_dist.main(scale=args.scale),
        "fig6": lambda: fig6_partition_methods.main(scale=args.scale),
        "table2": lambda: table2_spmv.main(scale=min(args.scale * 1.5, 1.0)),
        "fig11": lambda: fig11_transactions.main(scale=min(args.scale * 1.5, 1.0)),
        "fig12": lambda: fig12_cache_types.main(),
        "table3": lambda: table3_block_size.main(),
        "fig13": lambda: fig13_apps.main(),
        "hier": lambda: hierarchy_bench.main(),
        "svc": lambda: svc_service.main(scale=args.scale),
        "svc_streaming": lambda: svc_streaming.main(scale=args.scale),
        "svc_multitenant": lambda: svc_multitenant.main(scale=args.scale),
        "svc_batched": lambda: svc_batched.main(scale=args.scale),
        "svc_chaos": lambda: svc_chaos.main(scale=args.scale),
        "perf": lambda: perf_stages.main(scale=args.scale),
        "roofline": lambda: roofline.main(),
    }
    only = set(args.only.split(",")) if args.only else None
    if only and not only <= sections.keys():
        raise SystemExit(f"unknown section(s): {sorted(only - sections.keys())}")
    results: dict = {"scale": args.scale, "sections": {}, "section_time_s": {}}
    profiler = None
    if args.profile:
        import cProfile

        profiler = cProfile.Profile()
    t_all = time.perf_counter()
    for name, fn in sections.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        if profiler is not None:
            profiler.enable()
        try:
            out = fn()
        finally:
            if profiler is not None:
                profiler.disable()
        dt = time.perf_counter() - t0
        results["sections"][name] = out
        results["section_time_s"][name] = dt
        print(f"[{name} done in {dt:.1f}s]")
    results["total_time_s"] = time.perf_counter() - t_all
    if profiler is not None:
        import pstats

        print("\n== cProfile: top 20 by cumulative time ==")
        pstats.Stats(profiler).sort_stats("cumulative").print_stats(20)
    print(f"\nall benchmarks done in {results['total_time_s']:.1f}s")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2, default=_jsonable)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
