"""Device time per solve under the `zeus.phase2.update` scope less the
Pallas kernels' ops in it, in ms: what stages the update kernel's operands
(the pad of H and of dx, dg, g to the lane width, the slice back to D, the
copies). The kernel itself is `update_kernel_ms` (bench/scopes.py)."""
import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "zeus.phase2.update", kernels=False)
