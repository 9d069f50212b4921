"""Device time per solve under the `zeus.phase2.ladder` scope, in ms: the
speculative Armijo ladder's trial points, its value kernel over the rungs
and the accept select (bench/scopes.py)."""
import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "zeus.phase2.ladder")
