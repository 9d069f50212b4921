"""Pallas kernel: batched search direction p = -H g (Alg. 4 line 10).

Tiled batched matvec. For large B we process a *tile of lanes* per grid
step so the MXU sees a (TB·D, D)×(D,) workload per block instead of a thin
single matvec; H tiles stream HBM→VMEM once each.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def direction_body(H, g):
    """In-kernel body: p = -H·g for H (TB, D, D), g (TB, D) -> (TB, D).

    Batched matvec on the MXU (contract last dim of H with g per lane).
    Shared by the standalone kernel below and the sweep megakernel."""
    p = jax.lax.dot_general(
        H, g, (((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )  # (TB, D)
    return -p


def _direction_kernel(h_ref, g_ref, out_ref):
    out_ref[...] = direction_body(h_ref[...], g_ref[...]).astype(out_ref.dtype)


def direction_pallas(H, g, *, lane_tile: int = 8, interpret=False):
    B, D, _ = H.shape
    tb = min(lane_tile, B)
    # Pad the lane axis up to a tile multiple instead of shrinking the tile
    # to whatever divides B (which degraded to tb=1 for prime B). Padded
    # lanes are H=0, g=0 rows; the matvec is lane-independent, so their
    # garbage output is sliced off below — exact for the real lanes.
    Bp = ((B + tb - 1) // tb) * tb
    if Bp != B:
        H = jnp.pad(H, ((0, Bp - B), (0, 0), (0, 0)))
        g = jnp.pad(g, ((0, Bp - B), (0, 0)))
    out = pl.pallas_call(
        _direction_kernel,
        grid=(Bp // tb,),
        in_specs=[
            pl.BlockSpec((tb, D, D), lambda b: (b, 0, 0)),
            pl.BlockSpec((tb, D), lambda b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((tb, D), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, D), H.dtype),
        interpret=interpret,
    )(H, g)
    return out[:B]
