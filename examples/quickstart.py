"""Quickstart: minimize the 5-D Rastrigin function with ZEUS.

    PYTHONPATH=src python examples/quickstart.py

Reproduces the paper's core loop: PSO warm-start -> parallel multistart
quasi-Newton through the unified engine (solver selected by name from the
registry, lanes chunked to bound phase-2 memory) -> early stop at
required_c convergences -> confidence report from solution clustering
(§VII-B). Swap `solver="lbfgs"` to run the O(mD)-state strategy instead.

Then re-runs phase 1 with `phase1="meanfield"` — the mean-field consensus
swarm (DESIGN.md §18) that replaces the paper swarm's personal/global-best
machinery with one softmax-weighted consensus point, the strategy to reach
for at 10^6+ particles.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import (
    BFGSOptions,
    MeanFieldPSOOptions,
    PSOOptions,
    ZeusOptions,
    cluster_solutions,
    get_objective,
    zeus_jit,
)

DIM = 5


def main():
    obj = get_objective("rastrigin")
    opts = ZeusOptions(
        pso=PSOOptions(n_particles=2048, iter_pso=8),
        bfgs=BFGSOptions(iter_bfgs=100, theta=1e-4, required_c=400,
                         ad_mode="forward"),  # forward = the paper's dual AD
        solver="bfgs",  # engine registry name; "lbfgs" for limited memory
        lane_chunk=512,  # phase 2 runs 2048 lanes as 4 vmapped chunks
    )
    run = zeus_jit(obj.fn, DIM, obj.lower, obj.upper, opts)

    key = jax.random.key(0)
    res = run(key)

    x_star = obj.x_star(DIM)
    err = float(jnp.linalg.norm(res.best_x - x_star))
    print(f"best f        : {float(res.best_f):.3e}")
    print(f"best x        : {np.asarray(res.best_x).round(6)}")
    print(f"euclidean err : {err:.3e}  (paper threshold: 0.5 for 'correct')")
    print(f"converged     : {int(res.n_converged)} lanes "
          f"(required_c={opts.bfgs.required_c})")

    report = cluster_solutions(res.raw, radius=0.25)
    print("clusters      :", report.summary())
    assert err < 0.5, "did not land in the global basin"
    print("OK — global basin found")

    # The same solve with the mean-field phase 1: only the strategy switch
    # and its options change; phase 2 consumes the start set unchanged.
    # 2048 particles here so the example stays quick — the point of the
    # strategy is that n_particles scales to 10^6+ (state is just
    # {position, velocity}; the swarm couples through one O(D) consensus
    # point instead of a global argmin). At this small swarm size the
    # paper swarm's exploitative gbest usually wins the race to the exact
    # global basin; what the consensus swarm demonstrates here is the
    # *bias*: its start set lands phase 2 in the lowest shell of basins
    # (best_f ~ 1), where unbiased uniform multistart with the same 2048
    # lanes typically polishes to best_f ~ 7 on 5-D Rastrigin. The
    # per-objective-row basin-coverage win is measured and CI-gated in
    # benchmarks/engine_bench.py (the `meanfield` section).
    mf_opts = ZeusOptions(
        phase1="meanfield",
        meanfield=MeanFieldPSOOptions(n_particles=2048, iter_pso=8,
                                      beta=30.0, noise="anisotropic"),
        bfgs=opts.bfgs,
        solver=opts.solver,
        lane_chunk=opts.lane_chunk,
    )
    mf_run = zeus_jit(obj.fn, DIM, obj.lower, obj.upper, mf_opts)
    mf_res = mf_run(jax.random.key(1))
    mf_err = float(jnp.linalg.norm(mf_res.best_x - x_star))
    print(f"meanfield f   : {float(mf_res.best_f):.3e}   err {mf_err:.3e}")
    assert float(mf_res.best_f) < 3.0, (
        "mean-field starts should land phase 2 in the lowest basin shell")
    print("OK — mean-field starts landed in the lowest basin shell")


if __name__ == "__main__":
    compile_cache.enable()
    main()
