"""Benchmark runner — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig3,...]

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.record).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale settings")
    ap.add_argument("--only", default=None, help="comma-separated module keys")
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed, recorded in every BENCH_*.json")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        fig3_serverless_speedup,
        fig4_scaling,
        fig5_compression,
        fig6_sync_async,
        fig7_faults_coldstart,
        fig8_topology_scaling,
        fig9_sharded_aggregation,
        fig10_cost_time_frontier,
        fig11_engine_scaling,
        fig12_byzantine,
        fig13_fused_compression,
        fig14_auto_scheduler,
        roofline,
        table1_resource_stages,
        table2_3_cost,
    )
    from benchmarks.common import csv_header, record

    suites = {
        "table1": table1_resource_stages,
        "fig3": fig3_serverless_speedup,
        "table2_3": table2_3_cost,
        "fig4": fig4_scaling,
        "fig5": fig5_compression,
        "fig6": fig6_sync_async,
        "fig7": fig7_faults_coldstart,
        "fig8": fig8_topology_scaling,
        "fig9": fig9_sharded_aggregation,
        "fig10": fig10_cost_time_frontier,
        "fig11": fig11_engine_scaling,
        "fig12": fig12_byzantine,
        "fig13": fig13_fused_compression,
        "fig14": fig14_auto_scheduler,
        "roofline": roofline,
    }
    if args.only:
        keys = args.only.split(",")
        suites = {k: v for k, v in suites.items() if k in keys}

    csv_header()
    failures = []
    for name, mod in suites.items():
        t0 = time.time()
        try:
            mod.run(quick=not args.full, seed=args.seed)
            record(f"suite/{name}", (time.time() - t0) * 1e6, "status=ok")
        except Exception as e:  # pragma: no cover
            failures.append(name)
            traceback.print_exc()
            record(f"suite/{name}", (time.time() - t0) * 1e6, f"status=FAILED:{e!r}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
