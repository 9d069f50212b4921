"""Device time per solve under the `zeus.phase2.fused_sweep` scope, in ms:
on the megakernel path, the fused sweep kernel (the whole Armijo ladder,
the accept and the guarded update in one Pallas launch per chunk-sweep) and
the descent safeguard and Armijo thresholds around it (bench/scopes.py)."""
import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "zeus.phase2.fused_sweep")
