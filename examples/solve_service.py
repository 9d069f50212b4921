"""Continuous-batching solve service (DESIGN.md §16).

    PYTHONPATH=src python examples/solve_service.py

Registers three named problems, submits a staggered request stream into
one SolveService, and drains it: requests are admitted into freed lane
slots of each problem's always-running pool mid-flight — the LLM-serving
continuous-batching idea transplanted to multistart optimization. The
result of each request is array-equal to running it alone (same seed,
same pool width): traffic never changes anyone's answer.
"""
import numpy as np

from repro import compile_cache
from repro.core import CONVERGED, BFGSOptions, ZeusOptions
from repro.serve.service import (
    ProblemRegistry,
    SolveRequest,
    SolveService,
    solo_reference,
)


def main():
    opts = ZeusOptions(bfgs=BFGSOptions(iter_bfgs=60, theta=1e-4,
                                        ad_mode="reverse",
                                        sweep_mode="batched"))
    registry = ProblemRegistry()
    registry.register("rastrigin:4", "rastrigin", 4, opts=opts)
    registry.register("ackley:2", "ackley", 2, opts=opts)
    registry.register("rosenbrock:3", "rosenbrock", 3, opts=opts)

    service = SolveService(registry, slots=8, max_queue=32)

    # staggered deterministic stream: a second wave arrives while the
    # first is mid-solve and is admitted into slots as they free up
    rids = [service.submit(SolveRequest(name, seed=i, n_starts=4))
            for i, name in enumerate(registry.names())]
    service.pump()  # one segment boundary: harvest + admit + sweep
    rids += [service.submit(SolveRequest(name, seed=10 + i, n_starts=2,
                                         iter_max=40))
             for i, name in enumerate(registry.names())]

    results = service.drain()
    for rid in rids:
        r = results[rid]
        flag = "converged" if r.status == CONVERGED else "diverged"
        print(f"rid={rid} {r.problem:<13s} {flag:<10s} "
              f"best_f={r.best_f:.3e} lanes={len(r.lanes)} "
              f"admit={r.admit_latency_s * 1e3:.1f}ms")

    # the continuous-batching contract: busy pool == alone in the pool
    rid = rids[0]
    ref = solo_reference(registry.get(results[rid].problem),
                         service.request(rid), slots=service.slots)
    same = all(
        np.array_equal(lane.x, np.asarray(ref.x)[i])
        for i, lane in enumerate(results[rid].lanes))
    print(f"rid={rid} trajectory identical to solo run: {same}")

    st = service.stats()
    print(f"{st['n_done']} requests done; admit p95 = "
          f"{st['admit_latency_sweeps_p95']:.0f} sweeps; "
          f"{st['solves_per_sec']:.2f} solves/s (incl. compile)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
