"""Share of the traced window in which no operation ran on the chip:
1 - (union of device-op intervals) / window, from the profiler's trace."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_share
