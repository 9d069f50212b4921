"""Pallas TPU kernels for the batched BFGS inverse-Hessian update.

The paper measures the Hessian update as the dominant BFGS cost (§IV-C).
On TPU we restructure it for the memory hierarchy instead of porting the
CUDA thread loop:

  * the algebra is the expanded O(D²) form
        u = H δg,  s = δgᵀ u,  ρ = 1/(δxᵀ δg)
        H' = H − ρ(u δxᵀ + δx uᵀ) + (ρ²s + ρ) δx δxᵀ
    i.e. ONE matvec + three rank-1s fused into a single VMEM pass — vs the
    paper's literal V H Vᵀ triple product (two D×D×D matmuls). The literal
    form is kernels/ref.py's oracle; algebraic equality is asserted in tests.
  * `update_direction_kernel` additionally fuses the *next* search direction
    p' = −H' g' into the same pass, so H is read from HBM once and written
    once per BFGS iteration (2·D² transfers instead of 3·D² — the dominant
    roofline term of the whole optimizer).

Two layouts of the H stack, chosen by D alone (`lane_minor`):

  * D ≤ LANE_MINOR_MAX_DIM (the batched sweep's guarded update only):
    lanes on the 128-wide minor axis. H arrives as (D, D, B), the rows of
    every lane's H stacked by row index, and one grid step updates a tile
    of TB lanes with f32 multiply-adds on the VPU, D unpadded. A lane's
    (D, D) H costs D·⌈D/8⌉·8·4 B of HBM and VMEM (640 B at D = 10) where
    the padded (128, 128) tile below costs 64 KiB.
  * larger D, and `bfgs_update` / `update_direction` at every D: one grid
    step = one lane's full (Dp, Dp) update resident in VMEM, its matvecs
    on the MXU. ops.py zero-pads D to Dp, a multiple of 128, so the tiles
    stay aligned; zero padding is exact for this update (all extra terms
    vanish: padded components of δx, δg are 0). The double-buffered H-in
    and H-out blocks take 4·Dp²·4 B (16 MiB at Dp = 1024, the whole
    default scoped-VMEM limit on v5e), so `vmem_params` raises the limit
    above Dp = 512; the v5e compiler accepts every kernel here at D = 128,
    512 and 1024 (tests/test_tpu_compile.py).

Neither layout has a cross-lane term, so these kernels take any B —
including the small power-of-two active-lane buckets the engine's
compacted sweeps gather (engine.compact_every): a lane's update is
bit-identical whatever batch, or lane tile, it rides in, which is what
makes compaction's exact-parity contract hold through the kernel path.
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


# Largest D whose guarded update runs lane-minor. VMEM alone would allow a
# little more: at the smallest lane tile (TB = 128) the H-in and H-out
# blocks, double-buffered, plus one (D, D, TB) block of temporaries take
# 5·D·⌈D/8⌉·8·4·128 B, 10 MiB at D = 64, and reach the 16 MiB default
# scoped-VMEM limit (which `vmem_params` leaves in place) near D = 80.
# The code is the tighter limit: the row loops below unroll at trace time
# into about 11·D·⌈D/8⌉ vector ops per 128 lanes (≈ 5,600 at D = 64), four
# times as many per doubling of D, while past 64 the per-lane MXU kernel's
# (128, 128) tile is at least half full.
LANE_MINOR_MAX_DIM = 64

# Lanes per grid step of the lane-minor kernel: as many as fill this share
# of the scoped-VMEM limit (half of 16 MiB), at most _MAX_LANE_TILE, so the
# pipeline still overlaps a few tiles' DMAs at B = 8192. On one v5e a
# launch at B = 8192, D = 10 took 10.8 µs with 1,024-lane tiles, against
# 11.3 (256), 11.0 (512), 12.1 (2,048) and 16.2 µs (4,096).
_LANE_TILE_VMEM = 8 << 20
_MAX_LANE_TILE = 1024
_LANE = 128


def lane_minor(d: int) -> bool:
    """Does a D-dim guarded update run lane-minor? A function of D alone,
    shared by ops.guarded_update_direction and the sweep megakernel so both
    take the same per-lane arithmetic (`update_direction_rows`)."""
    return d <= LANE_MINOR_MAX_DIM


def lane_tile(d: int, b: int) -> int:
    """Lanes per grid step for a lane-minor update of b lanes at dim d: a
    multiple of 128 whose H-in/H-out blocks, double-buffered, and one block
    of temporaries (5 blocks of d·⌈d/8⌉·8 f32 per lane) fit
    _LANE_TILE_VMEM, and no wider than b rounded up to 128."""
    per_lane = 5 * d * (-(-d // 8) * 8) * 4
    fit = max(_LANE, _LANE_TILE_VMEM // per_lane // _LANE * _LANE)
    return min(fit, _MAX_LANE_TILE, -(-b // _LANE) * _LANE)


def update_direction_rows(row, at, dx, dg, gn, rho, d, interpret):
    """Guarded ρ-form H' and p' = −H'g' row by row, for d ≤ LANE_MINOR_MAX_DIM.

    `row(j)` is row j of H and `at(v, j)` component j of a vector, each
    shaped to broadcast against the vectors dx, dg, gn (a lane-minor tile's
    (D, TB) rows, or one lane's (1, Dp) row). H is symmetric, so its rows
    are its columns:

        u = Σ_j H[j]·δg_j,  s = Σ_j δg_j·u_j,  c = ρ²s + ρ
        H'[j] = H[j] + a·δx_j + δx·w_j,  a = −ρu,  w = c·δx − ρu
        p' = −Σ_j H'[j]·g'_j

    H' is the ρ-form H − ρ(u δxᵀ + δx uᵀ) + c δx δxᵀ with its three
    rank-1 terms regrouped into two, no term dropped: four f32 VPU ops per
    element of H' instead of eight. Every sum runs over the true d in
    ascending j, so a lane's arithmetic depends on neither the layout nor
    the batch it rides in. ρ = 0 with zeroed (δx, δg) leaves every row of
    H exactly.

    In interpret mode XLA's CPU backend contracts a multiply that feeds an
    add into an FMA, or not, depending on the fusion around it, so the
    same lane would round differently here and in the sweep megakernel.
    There every product that feeds an add is first multiplied by a 1 the
    compiler cannot fold (ρ·0 + 1; ρ is finite, by the engine's curvature
    guard), so an FMA formed from it rounds as the plain add does. The
    compiled kernel takes the products as they are.
    Returns ([H'[0], ..., H'[d-1]], p')."""
    if interpret:
        one = rho * 0 + 1

        def rnd(x):
            return x * one
    else:
        def rnd(x):
            return x

    u = rnd(row(0) * at(dg, 0))
    for j in range(1, d):
        u = u + rnd(row(j) * at(dg, j))
    s = rnd(at(dg, 0) * at(u, 0))
    for j in range(1, d):
        s = s + rnd(at(dg, j) * at(u, j))
    a = -rho * u
    w = rnd((rnd(rho * rho * s) + rho) * dx) + rnd(a)
    rows = [row(j) + rnd(a * at(dx, j)) + rnd(dx * at(w, j))
            for j in range(d)]
    p = rnd(rows[0] * at(gn, 0))
    for j in range(1, d):
        p = p + rnd(rows[j] * at(gn, j))
    return rows, -p


def lane_update_direction(H, dx, dg, gn, rho, d, interpret):
    """One lane's guarded H' and p' on its zero-padded (Dp, Dp) tile, by the
    rule the staged guarded update takes at the true dim d: row by row
    (`update_direction_rows`) when lane_minor(d), else the MXU body. The
    sweep megakernel's update step. dx/dg/gn (Dp,), ρ a scalar."""
    if not lane_minor(d):
        return update_direction_body(H, dx, dg, gn, rho)
    rows, p = update_direction_rows(
        lambda j: H[j:j + 1], lambda v, j: v[:, j:j + 1],
        dx[None], dg[None], gn[None], jnp.reshape(rho, (1, 1)), d, interpret)
    # rows past d stay H's zero padding: u and δx vanish there
    return jnp.concatenate(rows + [H[d:]], axis=0), p[0]


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


def _guarded_update_direction_kernel(lane_minor_tile, interpret, h_ref,
                                     dx_ref, dg_ref, gnew_ref, rho_ref,
                                     hout_ref, pout_ref):
    """Batch-level guarded variant: ρ comes in precomputed per lane.

    The engine's curvature guard (DESIGN.md §8) lifts to the batch level by
    passing ρ = 0 for guarded/frozen lanes: with ρ = 0 and zeroed (δx, δg)
    every update term vanishes, so H' = H exactly and p' = -H g' — no
    second read of H to undo a discarded update.

    `lane_minor_tile`: a grid step is TB lanes on the minor axis, blocks
    H (D, D, TB), vectors (D, TB), ρ (1, TB), updated row by row
    (`update_direction_rows`). Otherwise one lane per grid step, blocks
    H (1, Dp, Dp), vectors (1, 1, Dp), ρ (1, 1, 1), on the MXU."""
    if lane_minor_tile:
        rows, p = update_direction_rows(
            lambda j: h_ref[j], lambda v, j: v[j:j + 1], dx_ref[...],
            dg_ref[...], gnew_ref[...], rho_ref[...], h_ref.shape[0],
            interpret)
        for j, r in enumerate(rows):
            hout_ref[j] = r.astype(hout_ref.dtype)
        pout_ref[...] = p.astype(pout_ref.dtype)
        return
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
    """Fused guarded H' + p' for the batched sweep path, one lane per grid
    step: H (B, Dp, Dp), vectors (B, Dp), rho (B,) per lane, 0 where the
    curvature guard (or frozen-lane masking) disables the update."""
    B, D, _ = H.shape
    Hn, p = pl.pallas_call(
        functools.partial(_guarded_update_direction_kernel, False, interpret),
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


def guarded_update_direction_lanes_pallas(H, dx, dg, g_new, rho, *,
                                          interpret=False):
    """The guarded H' + p' lane-minor, for D ≤ LANE_MINOR_MAX_DIM: H (D, D, B)
    with H[j, :, b] row j of lane b's H, dx/dg/g_new (D, B), rho (B,).

    B is zero-padded to a multiple of the lane tile TB (`lane_tile`), padded
    lanes carry ρ = 0, and they are sliced off. A block's leading dims are
    the array's, so Mosaic takes (D, D, TB) and (D, TB) blocks at any D.
    Returns H' (D, D, B) and p' (D, B)."""
    D, _, B = H.shape
    tb = lane_tile(D, B)
    Bp = -(-B // tb) * tb

    def lanes(a):
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, Bp - B)])

    mat = pl.BlockSpec((D, D, tb), lambda b: (0, 0, b))
    vec = pl.BlockSpec((D, tb), lambda b: (0, b))
    Hn, p = pl.pallas_call(
        functools.partial(_guarded_update_direction_kernel, True, interpret),
        grid=(Bp // tb,),
        in_specs=[mat, vec, vec, vec, pl.BlockSpec((1, tb), lambda b: (0, b))],
        out_specs=[mat, vec],
        out_shape=[
            jax.ShapeDtypeStruct((D, D, Bp), H.dtype),
            jax.ShapeDtypeStruct((D, Bp), H.dtype),
        ],
        interpret=interpret,
    )(lanes(H), lanes(dx), lanes(dg), lanes(g_new), lanes(rho[None]))
    return Hn[..., :B], p[:, :B]
