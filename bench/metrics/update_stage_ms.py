"""Device time per solve under the `zeus.phase2.update` scope less the
Pallas kernels' ops in it, in ms: what stages the update kernel's operands.
At D ≤ 64, where the kernel takes H lane-minor and unpadded, that is the
guard's selects on δx, δg and ρ and the chunk's H sliced out of the lane
stack (the transposes to and from the lane-minor layout mostly compile to
bitcasts); above 64, the pad of H and of dx, dg, g to the lane width, the
slice back to D and the copies. The kernel itself is `update_kernel_ms`
(bench/scopes.py)."""
import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "zeus.phase2.update", kernels=False)
