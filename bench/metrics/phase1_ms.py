"""Device time per solve under the `zeus.phase1` scope, in ms: swarm init
and the PSO or mean-field steps (bench/scopes.py)."""
import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "zeus.phase1")
