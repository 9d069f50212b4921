"""Benchmark harness entry point: one function per paper figure/table.

    PYTHONPATH=src python -m benchmarks.run [--only fig1,fig2,...]

Prints ``name,us_per_call,derived`` CSV rows.
"""
import argparse
import sys

from benchmarks import engine_bench, figures, kernels_bench
from repro import compile_cache

SUITES = {
    "fig1": figures.fig1_rastrigin_dimension_sweep,
    "fig2": figures.fig2_parallel_vs_sequential,
    "fig3": figures.fig3_pso_iteration_tradeoff,
    "fig4": figures.fig4_baselines_10d,
    "fig5": figures.fig5_dijet_fit,
    "fig6": figures.fig6_ackley_failure,
    "hessian_dominance": kernels_bench.hessian_update_dominance,
    "hessian_forms": kernels_bench.hessian_update_forms,
    "fused_obj": kernels_bench.fused_objective_gradient,
    "ad_modes": kernels_bench.ad_mode_scaling,
    "engine_chunk": kernels_bench.engine_chunked_lanes,
    "engine_solvers": kernels_bench.engine_solver_strategies,
    # writes BENCH_engine.json: the batched-vs-per_lane perf trajectory
    "engine_sweep": engine_bench.engine_sweep,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else set(SUITES)
    print("name,us_per_call,derived")
    for name, fn in SUITES.items():
        if name in only:
            print(f"# --- {name}: {fn.__doc__.splitlines()[0]}", file=sys.stderr)
            fn()


if __name__ == "__main__":
    compile_cache.enable()
    main()
