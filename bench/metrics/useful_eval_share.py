"""Objective rows pushed for lanes still active, over all rows the sweeps
evaluated (BFGSResult.eval_rows: ladder rungs and value+grad rows of the
whole lane stack, masked lanes and padding included). The active rows are
(ls_iters + 1) per active lane-sweep (bench/work.py: lane_sweeps)."""
import numpy as np

import work


def read(ctx):
    ladder = ctx.cfg["zeus"]["bfgs"].get("ls_iters", 20)
    used = total = 0
    for a in ctx.answers:
        s = work.lane_sweeps(a["n_evals"], ctx.problem.vg_cost(ctx.cfg), ladder)
        rows = int(np.asarray(a["eval_rows"]))
        if s is None or rows <= 0:
            return None
        used += int(s.sum()) * (ladder + 1)
        total += rows
    return used / total if total else None
