"""Pallas TPU kernel for the batched BFGS inverse-Hessian update.

The paper measures the Hessian update as the dominant BFGS cost (§IV-C).
On TPU we restructure it for the memory hierarchy instead of porting the
CUDA thread loop:

  * one grid step = one lane's full (D, D) update resident in VMEM. The
    double-buffered H-in and H-out blocks take 4·D²·4 B (16 MiB at
    D = 1024, the whole default scoped-VMEM limit on v5e), so `vmem_params`
    raises the limit above D = 512; the v5e compiler accepts every kernel
    here at D = 128, 512 and 1024 (tests/test_tpu_compile.py);
  * the algebra is the expanded O(D²) form
        u = H δg,  s = δgᵀ u,  ρ = 1/(δxᵀ δg)
        H' = H − ρ(u δxᵀ + δx uᵀ) + (ρ²s + ρ) δx δxᵀ
    i.e. ONE matvec + three rank-1s fused into a single VMEM pass — vs the
    paper's literal V H Vᵀ triple product (two D×D×D matmuls). The literal
    form is kernels/ref.py's oracle; algebraic equality is asserted in tests.
  * `update_direction_kernel` additionally fuses the *next* search direction
    p' = −H' g' into the same pass, so H is read from HBM once and written
    once per BFGS iteration (2·D² transfers instead of 3·D² — the dominant
    roofline term of the whole optimizer; see EXPERIMENTS.md §Perf).

Lane dims D are zero-padded to a multiple of 128 by ops.py so the MXU/VPU
tiles stay aligned; zero padding is exact for this update (all extra terms
vanish: padded components of δx, δg are 0).

The batch dim B is one grid step per lane with no cross-lane term, so these
kernels take any B — including the small power-of-two active-lane buckets
the engine's compacted sweeps gather (engine.compact_every): a lane's
update is bit-identical whatever batch it rides in, which is what makes
compaction's exact-parity contract hold through the kernel path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Scoped-VMEM budget of the one-lane (D, D) kernels here and in the sweep
# megakernel. Their double-buffered H-in and H-out blocks alone take
# 4·D²·4 B, which fills the compiler's default scoped limit (16 MiB on
# v5e) at D = 1024. The limit is raised to twice the blocks, leaving room
# for the body's D²-sized temporaries; the v5e compiler needs between
# 24 and 32 MiB at D = 1024 for the plain update.
_DEFAULT_SCOPED_VMEM = 16 << 20


def vmem_params(D):
    """TPU compiler params for a kernel holding one lane's (D, D) H tile
    (None where the default scoped-VMEM limit already fits it)."""
    need = 8 * D * D * 4
    if need <= _DEFAULT_SCOPED_VMEM:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need)


def matvec_body(H, v):
    """In-kernel single-lane matvec H (D, D) · v (D,) -> (D,) on the MXU.

    Every H·vector product in this file and in the sweep megakernel goes
    through this ONE shape — (D, D)×(D, 1) dot_general, fp32 operands and
    accumulate (Precision.HIGHEST; the CPU interpreter ignores it) — so
    per-lane rounding is identical whichever kernel a lane's update rides
    in (the megakernel parity contract depends on this)."""
    return jax.lax.dot_general(
        H, v[:, None], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )[:, 0]


def vdot_body(a, b):
    """In-kernel a·b for two (D,) rows, as a (1, D)×(D, 1) dot_general.

    Mosaic has no lowering for a vector·vector dot that returns a scalar,
    and a multiply-then-sum rounds differently from the dot the per-lane
    BFGS path takes (hessian_update_fast) in interpret mode."""
    return jax.lax.dot_general(
        a[None, :], b[:, None], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )[0, 0]


def hupdate_body(H, dx, dg, rho):
    """In-kernel body: ρ-form BFGS H' for ONE lane, H (D, D), dx/dg (D,).

        u = H δg,  s = δgᵀ u
        H' = H − ρ(u δxᵀ + δx uᵀ) + (ρ²s + ρ) δx δxᵀ

    ONE matvec + three rank-1s fused in VMEM. With ρ = 0 and zeroed
    (δx, δg) every term vanishes, so H' = H exactly — the batch-level
    curvature guard. Returns (H', u) — u is dead code for callers that
    don't need it and DCE'd."""
    u = matvec_body(H, dg)
    s = vdot_body(dg, u)
    coef = rho * rho * s + rho
    H_new = (
        H
        - rho * (u[:, None] * dx[None, :] + dx[:, None] * u[None, :])
        + coef * (dx[:, None] * dx[None, :])
    )
    return H_new, u


def update_direction_body(H, dx, dg, gn, rho):
    """In-kernel body: H' update + p' = -H' g_new for one lane."""
    H_new, _ = hupdate_body(H, dx, dg, rho)
    return H_new, -matvec_body(H_new, gn)


def _bfgs_update_kernel(h_ref, dx_ref, dg_ref, out_ref):
    """Grid step: one lane. Blocks: H (1, D, D), dx/dg (1, 1, D)."""
    dx, dg = dx_ref[0, 0], dg_ref[0, 0]
    rho = 1.0 / vdot_body(dx, dg)
    H_new, _ = hupdate_body(h_ref[0], dx, dg, rho)
    out_ref[0] = H_new.astype(out_ref.dtype)


def _update_direction_kernel(h_ref, dx_ref, dg_ref, gnew_ref, hout_ref, pout_ref):
    """Fused: H' update + p' = -H' g_new, one HBM read + write of H."""
    dx, dg = dx_ref[0, 0], dg_ref[0, 0]
    rho = 1.0 / vdot_body(dx, dg)
    H_new, p = update_direction_body(h_ref[0], dx, dg, gnew_ref[0, 0], rho)
    hout_ref[0] = H_new.astype(hout_ref.dtype)
    pout_ref[0, 0] = p.astype(pout_ref.dtype)


def _guarded_update_direction_kernel(h_ref, dx_ref, dg_ref, gnew_ref, rho_ref,
                                     hout_ref, pout_ref):
    """Batch-level guarded variant: ρ comes in precomputed per lane.

    The engine's curvature guard (DESIGN.md §8) lifts to the batch level by
    passing ρ = 0 for guarded/frozen lanes: with ρ = 0 and zeroed (δx, δg)
    every update term vanishes, so H' = H exactly and p' = -H g' — no
    second read of H to undo a discarded update."""
    H_new, p = update_direction_body(
        h_ref[0], dx_ref[0, 0], dg_ref[0, 0], gnew_ref[0, 0], rho_ref[0, 0, 0])
    hout_ref[0] = H_new.astype(hout_ref.dtype)
    pout_ref[0, 0] = p.astype(pout_ref.dtype)


# Per-lane layout: one lane per grid step, so every per-lane operand is
# viewed with a unit axis in front of its last dim — vectors (B, D) as
# (B, 1, D), scalars (B,) as (B, 1, 1) — and blocked (1, 1, D) / (1, 1, 1).
# Mosaic requires a block's last two dims to be (8, 128)-divisible or equal
# to the array's; a (1, D) block of a (B, D) array is neither.
def _mat_spec(D):
    return pl.BlockSpec((1, D, D), lambda b: (b, 0, 0))


def _vec_spec(D):
    return pl.BlockSpec((1, 1, D), lambda b: (b, 0, 0))


def _lane_rows(*arrays):
    """(B, D) -> (B, 1, D) and (B,) -> (B, 1, 1) views for the lane blocks."""
    return tuple(a.reshape(a.shape[0], 1, -1) for a in arrays)


def bfgs_update_pallas(H, dx, dg, *, interpret=False):
    """Batched H' for H (B, D, D), dx/dg (B, D). D should be 128-aligned."""
    B, D, _ = H.shape
    return pl.pallas_call(
        _bfgs_update_kernel,
        grid=(B,),
        in_specs=[_mat_spec(D), _vec_spec(D), _vec_spec(D)],
        out_specs=_mat_spec(D),
        out_shape=jax.ShapeDtypeStruct((B, D, D), H.dtype),
        compiler_params=vmem_params(D),
        interpret=interpret,
    )(H, *_lane_rows(dx, dg))


def update_direction_pallas(H, dx, dg, g_new, *, interpret=False):
    B, D, _ = H.shape
    Hn, p = pl.pallas_call(
        _update_direction_kernel,
        grid=(B,),
        in_specs=[_mat_spec(D)] + [_vec_spec(D)] * 3,
        out_specs=[_mat_spec(D), _vec_spec(D)],
        out_shape=[
            jax.ShapeDtypeStruct((B, D, D), H.dtype),
            jax.ShapeDtypeStruct((B, 1, D), H.dtype),
        ],
        compiler_params=vmem_params(D),
        interpret=interpret,
    )(H, *_lane_rows(dx, dg, g_new))
    return Hn, p.reshape(B, D)


def guarded_update_direction_pallas(H, dx, dg, g_new, rho, *, interpret=False):
    """Fused guarded H' + p' for the batched sweep path: rho (B,) per lane,
    0 where the curvature guard (or frozen-lane masking) disables the update."""
    B, D, _ = H.shape
    Hn, p = pl.pallas_call(
        _guarded_update_direction_kernel,
        grid=(B,),
        in_specs=[_mat_spec(D)] + [_vec_spec(D)] * 3 + [_vec_spec(1)],
        out_specs=[_mat_spec(D), _vec_spec(D)],
        out_shape=[
            jax.ShapeDtypeStruct((B, D, D), H.dtype),
            jax.ShapeDtypeStruct((B, 1, D), H.dtype),
        ],
        compiler_params=vmem_params(D),
        interpret=interpret,
    )(H, *_lane_rows(dx, dg, g_new, rho))
    return Hn, p.reshape(B, D)
