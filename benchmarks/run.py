"""Benchmark harness (deliverable d): one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per harness contract.  Modules:
  table1  — MS-MARCO-analogue pruning comparison (paper Table 1)
  table2  — design-choice ablations (paper Table 2)
  table3  — zero-shot domain shift (paper Table 3)
  fig1    — query-embedding geometry diagnostics (paper Fig. 1)
  fig3    — aggressive-pruning degradation, VP vs LPP (paper Fig. 3)
  fig45   — position analyses (paper Figs. 4-5)
  fig6    — ME vs nDCG linearity (paper Fig. 6)
  speedup — VP vs LP-pruning wall-clock (the ~120x claim, §6.1.1)
  kernels — Pallas kernel micro-benches (fused vs materialized oracle)
  kernel_backends — reference vs fused/chunked hot paths; writes
            BENCH_kernel_backends.json (perf trajectory record)
"""

import sys
import traceback


def main() -> None:
    from benchmarks import (bench_fig1_geometry, bench_fig3_aggressive,
                            bench_fig45_positions, bench_fig6_me_ndcg,
                            bench_kernel_backends, bench_kernels,
                            bench_speedup,
                            bench_table1_indomain, bench_table2_ablation,
                            bench_table3_beir)
    only = set(sys.argv[1:])
    mods = [
        ("kernels", bench_kernels),
        ("kernel_backends", bench_kernel_backends),
        ("fig1", bench_fig1_geometry),
        ("table1", bench_table1_indomain),
        ("table2", bench_table2_ablation),
        ("table3", bench_table3_beir),
        ("fig3", bench_fig3_aggressive),
        ("fig45", bench_fig45_positions),
        ("fig6", bench_fig6_me_ndcg),
        ("speedup", bench_speedup),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in mods:
        if only and name not in only:
            continue
        try:
            mod.main()
        except Exception as e:
            failures += 1
            print(f"{name}/HARNESS_ERROR,0.0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
