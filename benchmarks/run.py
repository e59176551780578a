"""Run every benchmark (one per paper table/figure); print consolidated CSV.

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks import (bench_diurnal, bench_engine_throughput,
                        bench_fig1_cost_curves,
                        bench_fig2_quant, bench_fig3_penalty_heatmap,
                        bench_fig5_crossover, bench_kernels,
                        bench_overload, bench_plan_matrix, bench_planner,
                        bench_portfolio, bench_resilience,
                        bench_sensitivity, bench_table3_penalty,
                        bench_table4_sla,
                        bench_table5_stability, bench_table6_crosshw,
                        bench_table7_live)
from repro.launch.compile_cache import use_compile_cache

SUITES = (
    ("engine_throughput", bench_engine_throughput),
    ("plan_matrix", bench_plan_matrix),
    ("planner", bench_planner),
    ("portfolio", bench_portfolio),
    ("resilience", bench_resilience),
    ("diurnal", bench_diurnal),
    ("overload", bench_overload),
    ("fig1_cost_curves", bench_fig1_cost_curves),
    ("table3_penalty", bench_table3_penalty),
    ("fig2_quant", bench_fig2_quant),
    ("fig3_penalty_heatmap", bench_fig3_penalty_heatmap),
    ("table4_sla", bench_table4_sla),
    ("fig5_crossover", bench_fig5_crossover),
    ("sensitivity_5_7", bench_sensitivity),
    ("table5_stability", bench_table5_stability),
    ("table6_crosshw", bench_table6_crosshw),
    ("table7_live", bench_table7_live),
    ("kernel_micro", bench_kernels),
)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced request counts (~3x faster)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)
    use_compile_cache()

    t_all = time.time()
    for name, mod in SUITES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        print(f"\n{'=' * 72}\n=== {name} ===")
        mod.run(quick=args.quick)
        print(f"# {name} done in {time.time() - t0:.1f}s")
    print(f"\nALL BENCHMARKS DONE in {time.time() - t_all:.1f}s")


if __name__ == "__main__":
    main()
