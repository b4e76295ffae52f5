"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Figure benchmarks replay real
routing traces through the latency simulator; the roofline benchmark reads
the dry-run reports (run ``python -m repro.launch.dryrun`` first for that
section — missing reports degrade to an informative row, not an error).
"""
from __future__ import annotations

import sys
import time

from benchmarks.common import Csv
from repro.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    only = sys.argv[1] if len(sys.argv) > 1 else None
    from benchmarks import (bench_cache_aware, bench_decode, bench_faults,
                            bench_integrity, bench_prefill,
                            bench_serving_engine, bench_tiers,
                            fig2_step_size, fig3_batch_size,
                            fig4_diversity, fig7_overall_latency,
                            fig8_predictor_accuracy, fig9_cache_miss,
                            fig10_lru, fig11_cache_aware_routing,
                            fig_serving, kernels_bench, roofline)
    modules = {
        "fig2": fig2_step_size, "fig3": fig3_batch_size,
        "fig4": fig4_diversity, "fig7": fig7_overall_latency,
        "fig8": fig8_predictor_accuracy, "fig9": fig9_cache_miss,
        "fig10": fig10_lru, "fig11": fig11_cache_aware_routing,
        "serving": fig_serving, "decode": bench_decode, "serving_engine": bench_serving_engine,
        "prefill": bench_prefill, "cache_aware": bench_cache_aware,
        "faults": bench_faults, "tiers": bench_tiers,
        "kernels": kernels_bench, "roofline": roofline,
    }
    csv = Csv()
    print("name,us_per_call,derived")
    for name, mod in modules.items():
        if only and name != only:
            continue
        t0 = time.time()
        try:
            mod.run(csv)
            csv.add(f"_meta/{name}/wall_s", (time.time() - t0) * 1e6, "ok")
        except Exception as e:  # noqa: BLE001
            csv.add(f"_meta/{name}/error", 0.0,
                    f"{type(e).__name__}:{str(e)[:80]}")


if __name__ == "__main__":
    main()
