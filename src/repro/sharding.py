"""Logical-axis sharding rules (MaxText-style), divisibility-aware.

Arrays carry *logical* axis names; `logical_to_spec` resolves them to mesh
axes through a rule table, dropping any mesh axis that does not divide the
dimension (fallback = replicate that dim). This is what lets one model
definition serve a 2-device CPU smoke test, a 256-chip pod and a 512-chip
multi-pod mesh without edits — e.g. gemma2's 8 q-heads simply stop sharding
on a 16-wide model axis instead of erroring.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """jax.make_mesh with Auto axis types. jax defaults to Explicit axes,
    and the shardings here rely on the compiler propagating them."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))

# rule table: logical axis -> tuple of candidate mesh axes (joint sharding)
DEFAULT_RULES: dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": (),             # activations: sequence unsharded by default
    "seq_shard": ("model",),  # opt-in sequence parallelism
    "embed": (),            # d_model of activations
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "mlp": ("model",),      # FFN hidden
    "expert": ("data",),    # expert-parallel over the data axis (EP=DP trick)
    "moe_batch": ("pod",),  # token-group dim of dispatched MoE tensors
    "moe_embed": (),        # d_model of dispatched tokens (2D-TP variant)
    "moe_cap": (),          # capacity/slot dim of h (reduce-scatter variant)
    "moe_cap_out": (),      # capacity/slot dim of xout (RS-the-AR variant)
    "moe_embed_out": (),    # d_model of xout (post-down-proj)
    "expert_mlp_down": ("model",),  # w_down's f dim (default: row-parallel)
    "moe_embed_w": ("data",),       # w_down's d dim (default: fsdp over data)
    "expert_mlp": ("model",),
    "ssm_inner": ("model",),
    "ssm_state": (),
    "conv_width": (),
    "layers": (),           # stacked-scan layer dim
    "fsdp": ("data",),      # weight sharding over the data axis (ZeRO-3 style)
    "cache_batch": ("pod", "data"),
    "cache_seq": ("model",),  # flash-decode: softmax partials psum over model
    "cache_heads": ("model",),
}


import contextlib

_OVERRIDES: dict = {}


@contextlib.contextmanager
def rules_override(**overrides):
    """Temporarily override logical-axis rules (perf experiments / variants).

    Example:
        with rules_override(seq=("model",)):   # sequence parallelism
            lowered = jit(step).lower(...)
    """
    global _OVERRIDES
    saved = dict(_OVERRIDES)
    _OVERRIDES.update(overrides)
    try:
        yield
    finally:
        _OVERRIDES = saved


def active_rules() -> dict:
    if not _OVERRIDES:
        return DEFAULT_RULES
    merged = dict(DEFAULT_RULES)
    merged.update(_OVERRIDES)
    return merged


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def resolve_axis(
    mesh: Mesh, logical: Optional[str], dim_size: int, rules=None
) -> Union[None, str, Tuple[str, ...]]:
    """Mesh axes for one logical axis, keeping only a prefix of the candidate
    axes whose product divides dim_size."""
    if logical is None:
        return None
    rules = rules or active_rules()
    cand = rules.get(logical, ())
    chosen = []
    prod = 1
    for ax in cand:
        sz = mesh_axis_size(mesh, ax)
        if sz == 1:
            continue
        if dim_size % (prod * sz) == 0:
            chosen.append(ax)
            prod *= sz
        else:
            break  # keep prefix only: joint sharding must divide
    if not chosen:
        return None
    return chosen[0] if len(chosen) == 1 else tuple(chosen)


def logical_to_spec(
    mesh: Mesh, logical_axes: Sequence[Optional[str]], shape: Sequence[int],
    rules=None,
) -> P:
    assert len(logical_axes) == len(shape), (logical_axes, shape)
    used: set = set()
    parts = []
    for name, size in zip(logical_axes, shape):
        r = resolve_axis(mesh, name, size, rules)
        # one mesh axis may shard only one dim of a given array
        if r is None:
            parts.append(None)
            continue
        r_axes = (r,) if isinstance(r, str) else tuple(r)
        r_axes = tuple(a for a in r_axes if a not in used)
        if not r_axes:
            parts.append(None)
            continue
        used.update(r_axes)
        parts.append(r_axes[0] if len(r_axes) == 1 else r_axes)
    return P(*parts)


def named_sharding(mesh: Mesh, logical_axes, shape, rules=None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(mesh, logical_axes, shape, rules))


def constrain(x, mesh: Mesh, logical_axes, rules=None):
    """with_sharding_constraint by logical axes (no-op off-mesh)."""
    spec = logical_to_spec(mesh, logical_axes, x.shape, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
