"""The solve's required phase-2 work (bench/work.py) at the chip's peaks,
as a share of device busy time per solve, in %. The least time is the
larger of flops over the bf16 peak and bytes over the HBM peak."""
import numpy as np

import work


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0 or not ctx.answers:
        return None
    peak = work.peaks(ctx.device_kind)
    least = []
    for a in ctx.answers:
        w = work.solve_work(ctx.cfg, ctx.problem, a["n_evals"])
        if w is None:
            return None
        least.append(work.least_time(*w, peak))
    busy_per_solve = ctx.trace.busy_s / ctx.trace.n_solves
    return 100.0 * float(np.mean(least)) / busy_per_solve
