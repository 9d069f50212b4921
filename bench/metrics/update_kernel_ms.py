"""Device time per solve of the guarded BFGS update + direction Pallas
kernel, found in the trace by its kernel's name."""

KERNEL = "guarded_update_direction"


def read(ctx):
    if ctx.trace is None:
        return None
    t = sum(s for name, s in ctx.trace.op_s.items() if KERNEL in name)
    return t * 1e3 / ctx.trace.n_solves if t > 0 else None
