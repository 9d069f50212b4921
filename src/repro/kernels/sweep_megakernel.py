"""Pallas sweep megakernel: direction → ladder → accept → H-update fused.

After PRs 2–5 a batched BFGS sweep is still four separate XLA computations
— speculative-ladder launch, fused value+grad, guarded H-update+direction,
plus the glue between them — with the (B, D) x/g rows and the (B, D, D) H
tile round-tripping through HBM between every stage. The paper's core
claim (ZEUS §V) is that *residency* is what makes PSO+BFGS+AD competitive;
He et al. (arXiv 2404.11631) measure the same staged-launch overhead
dominating GPU simulation-optimization loops at exactly this granularity.
This module is the TPU answer: ONE `pl.pallas_call` per sweep whose grid
step keeps a lane's x, g, p, f-thresholds and full (Dp, Dp) H tile in VMEM
across all four stages.

Stage layout per grid step (one lane — see "why one lane" below):
  1. ladder   : trials (K, Dp) = x + αₖ·p from the HOST-constant α ladder
                (core/linesearch.ladder_alphas — the canonical ladder every
                Armijo program in this codebase indexes);
  2. values   : the fused objective's value body (fused_obj.objective_body)
                inline on the trial rows — the same row-independent body the
                staged ladder's pallas_call runs on (tn, Dp) tiles;
  3. accept   : first Armijo-accepted rung by min-index over the masked rung
                iota, against the PRECOMPUTED barriered thresholds rhs
                (K, 1) block (core/linesearch.armijo_thresholds — computed
                once outside so both programs compare against the
                bit-identical tensor); α selected by one-hot sum over the
                ladder constants (exact: one term survives, the rest are
                0.0 by `where`, never by multiplication);
  4. commit   : x' = x + α·p, fused value+grad body at x', curvature
                guard ρ (sliced to the TRUE dim so the reduction has the
                same length and order as the staged path's out-of-kernel
                `jnp.sum(dX·dG, -1)`), then the guarded ρ-form H' update
                and p' = −H'·g' through bfgs_update.lane_update_direction,
                which takes the rule the staged
                `_guarded_update_direction_kernel` takes at the true D:
                row by row over the true D (bfgs_update.
                update_direction_rows, the staged kernel's own per-lane
                arithmetic on its lane-minor tile) up to
                bfgs_update.LANE_MINOR_MAX_DIM, else the MXU body at the
                staged kernel's (Dp, Dp)×(Dp, 1) dot shapes.

Why one lane per grid step: exactness. Every reduction in the staged path
is either per-row (objective bodies), per-lane (the update kernel: a chain
of multiply-adds over the true D in ascending order, or a (Dp, Dp)×(Dp, 1)
dot per lane above the lane-minor bound), or out-of-kernel over the true D
(curv, ddir). Reproducing each lane's own op sequence per grid step makes
its arithmetic independent of B and bit-identical to the staged program
wherever the backend's reductions are length-stable — the same
batch-size-stability contract compaction already leans on. The staged
update is free to put TB lanes on the minor axis because its per-lane
arithmetic has no cross-lane term; the megakernel's other stages are not
(the ladder's (K, Dp) trial fan and the objective bodies are one lane's
rows here), so it keeps one lane per step and runs the update's row chain
on that lane's (Dp, Dp) tile.

Why the sequential fallback stays un-fused (PR 4 semantics): when
0 < ladder_len < K the staged adaptive ladder's fallback probes are
lax.cond-guarded LAUNCHES that short-circuit to zero objective work once
every lane has accepted — fusing them into the kernel would evaluate all
K−L residual rungs unconditionally for every lane (a kernel has no early
exit across grid steps), turning the adaptive ladder's row *savings* back
into full-ladder rows. So the short-ladder megakernel path reuses
`armijo_backtracking_batch` verbatim (launch #1, bit-identical α by
construction) and fuses everything after the accept — value+grad, guard,
H', p' — into the commit kernel (launch #2).

VMEM budget per grid step: H in + H out, each double-buffered by the
pipeline, is 4·Dp²·4 B, plus the rank-1 update temporaries; trials add
K·Dp·4 B and the vectors ~8·Dp·4 B. At the ops.MEGAKERNEL_MAX_DIM = 1024
cap the blocks alone fill the v5e compiler's default 16 MiB scoped-VMEM
limit, so both kernels take bfgs_update.vmem_params (a 32 MiB limit at
Dp = 1024, of the chip's 128 MiB VMEM) exactly like the guarded update.
Oversized D (and non-fused objectives, and rosenbrock at D not a multiple
of 128, where zero padding is inexact) are routed back to the staged path
by `engine.megakernel_unsupported_reason` before this module is reached.

There is deliberately NO jnp reference here: under REPRO_DISABLE_PALLAS=1
the engine's megakernel step delegates wholesale to `batch_lanes_step` —
the staged program IS the megakernel's reference semantics, bit-for-bit.
The interpret leg (CPU) runs the real fused bodies below; the
`jax.lax.optimization_barrier`s inside the body sit at exactly the staged
program's materialization points (pallas_call input/output boundaries).
XLA's CPU pipeline drops barriers before it fuses, so the update's row
chain does not lean on them: update_direction_rows keeps the CPU backend's
FMA contraction from rounding a lane differently in the two programs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bfgs_update import (_lane_rows, lane_update_direction,
                                       vmem_params)
from repro.kernels.fused_obj import objective_body

_CURV_EPS = 1e-10  # engine._CURV_EPS; kept literal to avoid a core import


def _barrier(x):
    """A staged-launch seam: barrier so consumers can't re-fuse across it.

    Placed where the staged program materializes an array at a pallas_call
    boundary (trial tensor in, ladder values out, x' in, value+grad out,
    (ρ, δx, δg) in). Elementwise-identity, so it never changes values —
    only prevents ULP-flipping recontraction across the seam. Interpret
    mode only: there XLA fuses the kernel body like any other program.
    Mosaic has no lowering for the barrier, and the compiled kernel keeps
    the body's op order without one."""
    return jax.lax.optimization_barrier(x)


def _seam_fn(interpret):
    return _barrier if interpret else (lambda x: x)


def _commit_tail(body, interpret, d, x, p, g, H, act, alpha):
    """Stage 4, shared by both kernels: step, value+grad, guard, H', p'.

    All inputs are one lane's (Dp,)/(Dp, Dp) rows; `d` is the true dim."""
    _seam = _seam_fn(interpret)
    x_new = _seam(x + alpha * p)
    f_new, g_row = body(x_new[None, :], with_grad=True)
    f_new, g_new = _seam(f_new[0]), _seam(g_row[0])
    dx = x_new - x
    dg = g_new - g
    # curvature guard on the TRUE dim: the staged path computes
    # jnp.sum(dX*dG, -1) on the engine's UNPADDED (B, D) arrays, so the
    # in-kernel reduction must see the same D elements in the same order —
    # a static slice of the padded rows, not a masked sum over Dp.
    curv = jnp.sum(dx[:d] * dg[:d])
    ok = jnp.logical_and(act, jnp.logical_and(
        jnp.isfinite(curv), curv > _CURV_EPS))
    # mirrors BatchedDenseBFGS.update_and_direction_batch's sanitisation
    rho = _seam(jnp.where(ok, 1.0 / jnp.where(ok, curv, 1.0), 0.0))
    dxs = _seam(jnp.where(ok, dx, 0.0))
    dgs = _seam(jnp.where(ok, dg, 0.0))
    h_new, p_new = lane_update_direction(H, dxs, dgs, g_new, rho, d,
                                         interpret)
    return x_new, f_new, g_new, h_new, p_new


def _full_sweep_kernel(body, interpret, d, exhaust_alpha, K,
                       x_ref, p_ref, g_ref, h_ref, act_ref, rhs_ref,
                       al_ref,
                       xo_ref, fo_ref, go_ref, ho_ref, po_ref,
                       ao_ref, ro_ref):
    """Grid step: ONE lane, all four stages. Blocks: x/p/g (1, 1, Dp),
    H (1, Dp, Dp), act (1, 1, 1) int32, rhs (1, K, 1) barriered thresholds,
    al (K, 1) the host ladder constants (an input because pallas kernels
    can't close over array constants — values still host-computed by
    linesearch.ladder_alphas)."""
    _seam = _seam_fn(interpret)
    x = x_ref[0, 0]
    p = p_ref[0, 0]
    act = act_ref[0, 0, 0] != 0

    # stages 1–2: the K-rung trial fan and its values, one VMEM pass
    al = al_ref[...]  # (K, 1) ladder constants, one rung per row
    trials = _seam(x[None, :] + al * p[None, :])  # (K, Dp)
    F = _seam(body(trials)[0])[:, None]  # (K, 1)

    # stage 3: first accepted rung. rung = min over accepted rung indices
    # (== the staged argmax-of-first-True when any accept, K when none —
    # exactly the staged exhaustion encoding).
    ok = F <= rhs_ref[0]
    kio = jax.lax.broadcasted_iota(jnp.int32, (K, 1), 0)
    rung = jnp.min(jnp.where(ok, kio, K)).astype(jnp.int32)
    # α by one-hot sum: the single selected ladder constant survives, every
    # other term is literally 0.0 — a selection, not an arithmetic blend.
    alpha_acc = jnp.sum(jnp.where(kio == rung, al, 0.0))
    alpha = jnp.where(rung < K, alpha_acc, jnp.asarray(exhaust_alpha))

    # stage 4: commit + guarded H-update + next direction
    x_new, f_new, g_new, h_new, p_new = _commit_tail(
        body, interpret, d, x, p, g_ref[0, 0], h_ref[0], act, alpha)
    _store_lane(xo_ref, fo_ref, go_ref, ho_ref, po_ref,
                x_new, f_new, g_new, h_new, p_new)
    _store_scalar(ao_ref, alpha)
    _store_scalar(ro_ref, rung)


def _store_scalar(ref, v):
    ref[0] = jnp.full((1, 1), v, ref.dtype)


def _store_lane(xo_ref, fo_ref, go_ref, ho_ref, po_ref,
                x_new, f_new, g_new, h_new, p_new):
    xo_ref[0, 0] = x_new.astype(xo_ref.dtype)
    _store_scalar(fo_ref, f_new)
    go_ref[0, 0] = g_new.astype(go_ref.dtype)
    ho_ref[0] = h_new.astype(ho_ref.dtype)
    po_ref[0, 0] = p_new.astype(po_ref.dtype)


def _commit_kernel(body, interpret, d,
                   x_ref, p_ref, g_ref, h_ref, act_ref, alpha_ref,
                   xo_ref, fo_ref, go_ref, ho_ref, po_ref):
    """Short-ladder commit: stage 4 only, α decided by the staged adaptive
    ladder (launch #1). One lane per grid step, same blocks as above."""
    outs = _commit_tail(
        body, interpret, d, x_ref[0, 0], p_ref[0, 0], g_ref[0, 0], h_ref[0],
        act_ref[0, 0, 0] != 0, alpha_ref[0, 0, 0])
    _store_lane(xo_ref, fo_ref, go_ref, ho_ref, po_ref, *outs)


def _lane_specs(D):
    """One lane per grid step: per-lane vectors (B, D) are viewed as
    (B, 1, D) and scalars (B,) as (B, 1, 1), so every block's last two dims
    equal the array's — the only legal Mosaic layout for a one-row block."""
    vec = pl.BlockSpec((1, 1, D), lambda b: (b, 0, 0))
    mat = pl.BlockSpec((1, D, D), lambda b: (b, 0, 0))
    scl = pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0))
    return vec, mat, scl


def _lane_shapes(B, D, dtype):
    """out_shape for (x', f', g', H', p') in the lane layout above."""
    return [
        jax.ShapeDtypeStruct((B, 1, D), dtype),
        jax.ShapeDtypeStruct((B, 1, 1), dtype),
        jax.ShapeDtypeStruct((B, 1, D), dtype),
        jax.ShapeDtypeStruct((B, D, D), dtype),
        jax.ShapeDtypeStruct((B, 1, D), dtype),
    ]


def _unlane(B, D, x, f, g, H, p, *scalars):
    """Back from the lane layout: (B, D) vectors, (B,) scalars."""
    return (x.reshape(B, D), f.reshape(B), g.reshape(B, D), H,
            p.reshape(B, D)) + tuple(s.reshape(B) for s in scalars)


def sweep_megakernel_full_pallas(name, X, P, G, H, active, rhs, alphas_np,
                                 *, dim=None, shrink=0.5, interpret=False):
    """The full-ladder megakernel: ONE launch for ladder+accept+commit.

    X/P/G (B, Dp), H (B, Dp, Dp), active (B,) bool, rhs (K, B) barriered
    Armijo thresholds, alphas_np the (K,) host ladder. `dim` is the true
    (unpadded) lane dim. Returns (x', f', g', H', p', α, rung) — padded
    shapes; callers slice."""
    B, D = X.shape
    d = dim if dim is not None else D
    K = int(alphas_np.shape[0])
    body = objective_body(name, d)
    npdt = alphas_np.dtype.type
    exhaust_alpha = npdt(alphas_np[-1] * npdt(shrink))  # staged alphas[-1]·shrink
    vec, mat, scl = _lane_specs(D)
    kernel = functools.partial(
        _full_sweep_kernel, body, interpret, d, exhaust_alpha, K)
    outs = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[vec, vec, vec, mat, scl,
                  pl.BlockSpec((1, K, 1), lambda b: (b, 0, 0)),
                  pl.BlockSpec((K, 1), lambda b: (0, 0))],
        out_specs=[vec, scl, vec, mat, vec, scl, scl],
        out_shape=_lane_shapes(B, D, X.dtype) + [
            jax.ShapeDtypeStruct((B, 1, 1), X.dtype),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        ],
        compiler_params=vmem_params(D),
        interpret=interpret,
    )(*_lane_rows(X, P, G), H, *_lane_rows(active.astype(jnp.int32)),
      rhs.T[:, :, None], jnp.asarray(alphas_np)[:, None])
    return _unlane(B, D, *outs)


def sweep_megakernel_commit_pallas(name, X, P, G, H, active, alpha,
                                   *, dim=None, interpret=False):
    """The commit megakernel: ONE launch for step+value_grad+guard+H'+p',
    with α already accepted by the staged adaptive ladder. Shapes as in
    sweep_megakernel_full_pallas, α (B,). Returns (x', f', g', H', p')."""
    B, D = X.shape
    d = dim if dim is not None else D
    body = objective_body(name, d)
    vec, mat, scl = _lane_specs(D)
    kernel = functools.partial(_commit_kernel, body, interpret, d)
    outs = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[vec, vec, vec, mat, scl, scl],
        out_specs=[vec, scl, vec, mat, vec],
        out_shape=_lane_shapes(B, D, X.dtype),
        compiler_params=vmem_params(D),
        interpret=interpret,
    )(*_lane_rows(X, P, G), H, *_lane_rows(active.astype(jnp.int32), alpha))
    return _unlane(B, D, *outs)
