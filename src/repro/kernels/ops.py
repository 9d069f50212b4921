"""jit'd public wrappers around the Pallas kernels.

Each op:
  * pads the lane dimension D to a multiple of 128 (MXU/VPU tile alignment;
    zero padding is exact for every op here — see bfgs_update.py docstring),
  * dispatches to the Pallas kernel on TPU, to interpret=True mode on CPU
    (so the same kernel body is validated everywhere), or to the jnp
    reference when REPRO_DISABLE_PALLAS=1.
"""
from __future__ import annotations

import contextlib
import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bfgs_update import (
    bfgs_update_pallas,
    guarded_update_direction_lanes_pallas,
    guarded_update_direction_pallas,
    lane_minor,
    update_direction_pallas,
)
from repro.kernels.direction import direction_pallas
from repro.kernels.fused_obj import fused_value_grad_pallas, fused_value_pallas
from repro.kernels.meanfield_step import meanfield_step_pallas
from repro.kernels.pso_step import pso_step_pallas

_LANE = 128  # TPU lane width


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas() -> bool:
    return os.environ.get("REPRO_DISABLE_PALLAS", "0") != "1"


def pallas_enabled() -> bool:
    """Public probe: do ops dispatch to Pallas kernels right now?

    The engine's megakernel sweep step checks this to decide between the
    fused launch and wholesale delegation to the staged batched step (the
    megakernel's reference semantics under REPRO_DISABLE_PALLAS=1 — there
    is no separate jnp reference for the fused sweep, by design)."""
    return _use_pallas()


def _interpret() -> bool:
    """Compile the kernels on a TPU; interpret them on the CPU backend, the
    test seam. Any other backend has no Pallas TPU lowering and is refused,
    so a kernel never runs interpreted where a device was meant."""
    backend = jax.default_backend()
    if backend in ("tpu", "cpu"):
        return backend == "cpu"
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or interpreted on the CPU; "
        f"the default backend is {backend!r}. Set REPRO_DISABLE_PALLAS=1 "
        f"to use the jnp references there.")


@contextlib.contextmanager
def reference_kernels_off_tpu():
    """Force the jnp reference paths (REPRO_DISABLE_PALLAS=1) while inside
    the context, off-TPU only; restores the previous value on exit.

    For benchmarks: off-TPU, Pallas interpret mode executes kernel grids as
    Python loops — meaningless for timing — so timed comparisons should run
    the XLA-compiled jnp schedules instead (benchmarks/engine_bench.py,
    launch/perf_lab.py --zeus)."""
    prev = os.environ.get("REPRO_DISABLE_PALLAS")
    if not _on_tpu():
        os.environ["REPRO_DISABLE_PALLAS"] = "1"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("REPRO_DISABLE_PALLAS", None)
        else:
            os.environ["REPRO_DISABLE_PALLAS"] = prev


def _pad_to(x: jnp.ndarray, size: int, axis: int) -> jnp.ndarray:
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _padded_dim(d: int) -> int:
    return ((d + _LANE - 1) // _LANE) * _LANE


# -- batched BFGS inverse-Hessian update -------------------------------------
def bfgs_update(H: jnp.ndarray, dx: jnp.ndarray, dg: jnp.ndarray) -> jnp.ndarray:
    """H (B, D, D), dx/dg (B, D) -> H' (B, D, D)."""
    if not _use_pallas():
        return ref.bfgs_update_ref(H, dx, dg)
    B, D, _ = H.shape
    Dp = _padded_dim(D)
    Hp = _pad_to(_pad_to(H, Dp, 1), Dp, 2)
    out = bfgs_update_pallas(
        Hp, _pad_to(dx, Dp, 1), _pad_to(dg, Dp, 1), interpret=_interpret()
    )
    return out[:, :D, :D]


def bfgs_update_single(H: jnp.ndarray, dx: jnp.ndarray, dg: jnp.ndarray) -> jnp.ndarray:
    """Single-lane variant used inside vmapped BFGS (core/bfgs.py)."""
    return bfgs_update(H[None], dx[None], dg[None])[0]


def bfgs_update_direction(H, dx, dg, g_new):
    """Fused H' + p' = -H' g_new. Returns (H', p')."""
    if not _use_pallas():
        return ref.update_direction_ref(H, dx, dg, g_new)
    B, D, _ = H.shape
    Dp = _padded_dim(D)
    Hp = _pad_to(_pad_to(H, Dp, 1), Dp, 2)
    Hn, p = update_direction_pallas(
        Hp,
        _pad_to(dx, Dp, 1),
        _pad_to(dg, Dp, 1),
        _pad_to(g_new, Dp, 1),
        interpret=_interpret(),
    )
    return Hn[:, :D, :D], p[:, :D]


def guarded_update_direction(H, dx, dg, g_new, rho):
    """Batch-level guarded fused pass for the engine's batched sweep path.

    rho (B,) is the precomputed curvature factor 1/(δxᵀδg), already zeroed
    for lanes whose update is disabled (curvature guard or frozen lane) —
    with ρ = 0 and zeroed (δx, δg) the update is exactly H' = H, so the
    guard costs no second read of H. Returns (H', p' = -H' g_new).

    Up to bfgs_update.LANE_MINOR_MAX_DIM the kernel takes the stack
    lane-minor and unpadded in D: H as (D, D, B), row j of every lane's H
    in H[j], and the vectors as (D, B). The transposes to and from the
    engine's (B, D, D) / (B, D) stay here."""
    if not _use_pallas():
        return ref.guarded_update_direction_ref(H, dx, dg, g_new, rho)
    B, D, _ = H.shape
    if lane_minor(D):
        Hn, p = guarded_update_direction_lanes_pallas(
            jnp.transpose(H, (1, 2, 0)), dx.T, dg.T, g_new.T, rho,
            interpret=_interpret())
        return jnp.transpose(Hn, (2, 0, 1)), p.T
    Dp = _padded_dim(D)
    Hp = _pad_to(_pad_to(H, Dp, 1), Dp, 2)
    Hn, p = guarded_update_direction_pallas(
        Hp,
        _pad_to(dx, Dp, 1),
        _pad_to(dg, Dp, 1),
        _pad_to(g_new, Dp, 1),
        rho,
        interpret=_interpret(),
    )
    return Hn[:, :D, :D], p[:, :D]


# -- batched direction --------------------------------------------------------
def direction(H: jnp.ndarray, g: jnp.ndarray) -> jnp.ndarray:
    if not _use_pallas():
        return ref.direction_ref(H, g)
    B, D, _ = H.shape
    Dp = _padded_dim(D)
    Hp = _pad_to(_pad_to(H, Dp, 1), Dp, 2)
    out = direction_pallas(Hp, _pad_to(g, Dp, 1), interpret=_interpret())
    return out[:, :D]


# -- fused PSO step -----------------------------------------------------------
def pso_step_update(x, v, px, gx, r1, r2, w, c1, c2):
    if not _use_pallas():
        return ref.pso_step_ref(x, v, px, gx, r1, r2, w, c1, c2)
    N, D = x.shape
    Dp = _padded_dim(D)
    x_new, v_new = pso_step_pallas(
        _pad_to(x, Dp, 1),
        _pad_to(v, Dp, 1),
        _pad_to(px, Dp, 1),
        _pad_to(gx, Dp, 0),
        _pad_to(r1, Dp, 1),
        _pad_to(r2, Dp, 1),
        w, c1, c2,
        interpret=_interpret(),
    )
    return x_new[:, :D], v_new[:, :D]


# -- fused mean-field PSO step --------------------------------------------------
def meanfield_step_update(x, v, xbar, xi, w, drift, sigma,
                          noise: str = "anisotropic"):
    """x/v/ξ (N, D), x̄ (D,) -> (x', v'): the fused drift + exploration-noise
    + position update of the mean-field swarm (DESIGN.md §18). `noise` is
    "isotropic" (row-norm envelope) or "anisotropic" (per-coordinate)."""
    if not _use_pallas():
        return ref.meanfield_step_ref(x, v, xbar, xi, w, drift, sigma, noise)
    N, D = x.shape
    # Lane-pad D only where the hardware needs it (TPU). Zero pad columns
    # are mathematically exact for both noise modes (d = 0 there), but the
    # widened isotropic row-norm reduction may RE-ASSOCIATE the sum and
    # round differently at ~1 ulp — so the interpret (CPU) leg runs
    # unpadded and stays bit-identical to the jitted reference.
    interp = _interpret()
    Dp = D if interp else _padded_dim(D)
    x_new, v_new = meanfield_step_pallas(
        _pad_to(x, Dp, 1),
        _pad_to(v, Dp, 1),
        _pad_to(xbar, Dp, 0),
        _pad_to(xi, Dp, 1),
        w, drift, sigma,
        isotropic=(noise == "isotropic"),
        interpret=interp,
    )
    return x_new[:, :D], v_new[:, :D]


# -- fused objective + gradient -------------------------------------------------
FUSED_OBJECTIVES = ("sphere", "rastrigin", "rosenbrock", "ackley")


def fused_value_grad(name: str, x: jnp.ndarray):
    """x (N, D) -> (f (N,), g (N, D)); analytic fused kernels where available.

    N is whatever batch the caller holds — including the small power-of-two
    active-lane buckets of the engine's compacted sweeps — and is padded up
    to the particle tile inside the pallas wrappers."""
    if name not in FUSED_OBJECTIVES or not _use_pallas():
        return getattr(ref, f"{name}_vg_ref")(x)
    N, D = x.shape
    Dp = _padded_dim(D)
    if name == "rosenbrock" and Dp != D:
        # zero padding is NOT exact for rosenbrock's coupled terms: the
        # boundary term (x_{D+1} - x_D^2) would be polluted. Use the ref.
        return ref.rosenbrock_vg_ref(x)
    # rastrigin: each zero pad column contributes A - A*cos(0) = 0 — exact.
    # ackley: padding is NOT exact (1/d normalizers, mean-cos), so the true
    # dim is baked into the kernel and pad columns are masked there.
    f, g = fused_value_grad_pallas(name, _pad_to(x, Dp, 1), dim=D,
                                   interpret=_interpret())
    return f, g[:, :D]


def fused_value(name: str, x: jnp.ndarray):
    """x (N, D) -> f (N,): value-only twin of fused_value_grad.

    Used by the speculative batched line search, where only trial values are
    needed. MUST agree with fused_value_grad's f to fp rounding (the Armijo
    test compares the two) — the value kernels repeat the fused kernels'
    value expressions verbatim, and every fallback takes f from the same
    code path fused_value_grad would use (XLA dead-code-eliminates the
    untouched gradient)."""
    if name not in FUSED_OBJECTIVES or not _use_pallas():
        return getattr(ref, f"{name}_vg_ref")(x)[0]
    N, D = x.shape
    Dp = _padded_dim(D)
    if name == "rosenbrock" and Dp != D:
        return ref.rosenbrock_vg_ref(x)[0]
    return fused_value_pallas(name, _pad_to(x, Dp, 1), dim=D,
                              interpret=_interpret())


# -- sweep megakernel ---------------------------------------------------------
# Cap on the PADDED lane dim for the fused sweep kernels: the per-grid-step
# working set is dominated by the double-buffered H in + H out blocks and
# the rank-1 update temporaries (kernels/sweep_megakernel.py docstring).
# The v5e compiler refuses Dp = 1024 at its default 16 MiB scoped-VMEM
# limit and accepts it with the 32 MiB that bfgs_update.vmem_params asks
# for; Dp = 128 and 512 fit the default (tests/test_tpu_compile.py).
MEGAKERNEL_MAX_DIM = 1024


def megakernel_supported_objective(name) -> bool:
    """Objectives whose value/value+grad bodies can run inside the sweep
    megakernel. A subset of FUSED_OBJECTIVES: every analytic body qualifies
    (rosenbrock's extra Dp == D condition is dimension-dependent and checked
    separately in engine.megakernel_unsupported_reason)."""
    return name in FUSED_OBJECTIVES


def sweep_megakernel_full(name, X, P, G, H, active, rhs, alphas_np):
    """ONE launch: ladder + accept + value_grad + guarded H' + p'.

    X/P/G (B, D) unpadded, H (B, D, D), active (B,) bool, rhs (K, B) the
    barriered Armijo thresholds (core/linesearch.armijo_thresholds),
    alphas_np the (K,) host ladder constants. Returns
    (x', f', g', H', p', α, rung) sliced back to D. No jnp reference —
    callers must route to the staged step when pallas is disabled."""
    if not _use_pallas():
        raise RuntimeError(
            "sweep_megakernel_full has no jnp reference; the engine "
            "delegates to batch_lanes_step under REPRO_DISABLE_PALLAS=1")
    from repro.kernels.sweep_megakernel import sweep_megakernel_full_pallas

    B, D = X.shape
    Dp = _padded_dim(D)
    Hp = _pad_to(_pad_to(H, Dp, 1), Dp, 2)
    x, f, g, Hn, p, alpha, rung = sweep_megakernel_full_pallas(
        name,
        _pad_to(X, Dp, 1),
        _pad_to(P, Dp, 1),
        _pad_to(G, Dp, 1),
        Hp,
        active,
        rhs,
        alphas_np,
        dim=D,
        interpret=_interpret(),
    )
    return (x[:, :D], f, g[:, :D], Hn[:, :D, :D], p[:, :D], alpha, rung)


def sweep_megakernel_commit(name, X, P, G, H, active, alpha):
    """ONE launch: step to x + α·p + value_grad + guarded H' + p', with α
    already accepted by the staged adaptive ladder (the short-ladder
    megakernel path's second and last launch). Returns (x', f', g', H', p')
    sliced back to D. No jnp reference (see sweep_megakernel_full)."""
    if not _use_pallas():
        raise RuntimeError(
            "sweep_megakernel_commit has no jnp reference; the engine "
            "delegates to batch_lanes_step under REPRO_DISABLE_PALLAS=1")
    from repro.kernels.sweep_megakernel import sweep_megakernel_commit_pallas

    B, D = X.shape
    Dp = _padded_dim(D)
    Hp = _pad_to(_pad_to(H, Dp, 1), Dp, 2)
    x, f, g, Hn, p = sweep_megakernel_commit_pallas(
        name,
        _pad_to(X, Dp, 1),
        _pad_to(P, Dp, 1),
        _pad_to(G, Dp, 1),
        Hp,
        active,
        alpha,
        dim=D,
        interpret=_interpret(),
    )
    return (x[:, :D], f, g[:, :D], Hn[:, :D, :D], p[:, :D])


# -- flash attention -----------------------------------------------------------
def flash_attention(q, k, v, *, causal=True, scale=None,
                    block_q=512, block_k=512):
    """Flash/Splash attention: q (B,Sq,H,hd), k/v (B,Sk,KV,hd) -> (B,Sq,H,hd).

    Sequence lengths must divide the block sizes after clamping (the LM
    substrate's shapes are powers of two; ragged tails fall back to ref)."""
    from repro.kernels.flash_attention import flash_attention as _fa
    if not _use_pallas():
        return ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)
    Sq, Sk = q.shape[1], k.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    if Sq % bq or Sk % bk:
        return ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)
    return _fa(q, k, v, causal=causal, scale=scale, block_q=bq, block_k=bk,
               interpret=_interpret())
