"""The share of the vmapped sweep loop's steps that a toy still needed:
over the traced solves, the sum of each toy's sweeps over T times the most
sweeps a toy of its solve ran (BFGSResult.iterations). Under jax.vmap the
while loop steps every toy of a solve until the slowest one stops; the
rest is what an engine that carries each lane's own problem could win
back. Only for a configuration with `toys_per_solve`."""
import numpy as np


def read(ctx):
    toys = ctx.cfg.get("toys_per_solve")
    if not toys or not ctx.answers:
        return None
    sweeps = np.array([int(a["iterations"]) for a in ctx.answers])
    sweeps = sweeps.reshape(-1, toys)  # a raised solve drops all its toys
    most = toys * int(sweeps.max(axis=1).sum())
    return float(sweeps.sum() / most) if most else None
