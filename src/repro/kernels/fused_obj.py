"""Pallas kernels: fused objective + gradient evaluation.

The paper runs forward-mode AD *inside* the CUDA kernel so value and
derivative share one traversal of the expression. The TPU analogue: one
VMEM pass per particle tile that emits f(x) and ∇f(x) together, sharing
subexpressions (e.g. Rastrigin's 2πx feeds both cos for the value and sin
for the gradient). Used by the hot path of PSO (values) and BFGS (both).

Supported analytically-fused objectives: sphere, rastrigin, rosenbrock,
ackley. Arbitrary objectives fall back to jax AD (ops.py).

Each objective is ONE row-wise body `f(x (N, Dp)) -> (f (N,), g (N, Dp))`
with a static `with_grad` flag: the value-only call traces exactly the
value subgraph the fused call traces (same expression objects), which is
what keeps the speculative line-search ladder's trial values and the
Armijo F0 rounding identically — previously enforced by keeping twin
kernels textually in sync, now by construction. The `pl.pallas_call`
wrappers below are thin shells over the bodies; the sweep megakernel
(kernels/sweep_megakernel.py) calls the same bodies inline so in-kernel
trial evaluation rounds like the staged launches.

Bodies are looked up through small factories taking the TRUE (unpadded)
lane dim: most ignore it (zero padding is exact for them), but ackley's
1/d normalizers and mean-cos term need the real d baked in, with padded
columns masked out of the value reductions."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rastrigin_body(x, *, with_grad=False):
    a = 10.0
    two_pi_x = (2.0 * jnp.pi) * x
    f = a * x.shape[-1] + jnp.sum(x * x - a * jnp.cos(two_pi_x), axis=-1)
    if not with_grad:
        return f, None
    g = 2.0 * x + (2.0 * jnp.pi * a) * jnp.sin(two_pi_x)
    return f, g


def _sphere_body(x, *, with_grad=False):
    f = jnp.sum(x * x, axis=-1)
    if not with_grad:
        return f, None
    return f, 2.0 * x


def _rosenbrock_body(x, *, with_grad=False):
    xi, xn = x[:, :-1], x[:, 1:]
    d = xn - xi * xi
    f = jnp.sum((1.0 - xi) ** 2 + 100.0 * d * d, axis=-1)
    if not with_grad:
        return f, None
    g = jnp.zeros_like(x)
    g = g.at[:, :-1].add(-2.0 * (1.0 - xi) - 400.0 * xi * d)
    g = g.at[:, 1:].add(200.0 * d)
    return f, g


def _ackley_body(x, *, d, with_grad=False):
    """Paper §V-B3. `d` is the true (unpadded) dim: the value normalizes by
    d and averages cos(2πx) over d columns, so cos(0) = 1 from zero padding
    would pollute both — padded columns are masked out of the cos sum (the
    x² sum is exact under zero padding already). The exp/sqrt subexpressions
    e1, e2 are shared between f and ∇f like rastrigin's 2πx is."""
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    two_pi_x = (2.0 * jnp.pi) * x
    s1 = jnp.sqrt(jnp.sum(x * x, axis=-1) / d)
    s2 = jnp.sum(jnp.where(col < d, jnp.cos(two_pi_x), 0.0), axis=-1) / d
    e1 = jnp.exp(-0.2 * s1)
    e2 = jnp.exp(s2)
    f = -20.0 * e1 - e2 + jnp.e + 20.0
    if not with_grad:
        return f, None
    # ∂f/∂x_i = 4 e1 x_i / (d s1) + (2π/d) sin(2πx_i) e2. At the origin the
    # gradient is genuinely undefined (s1 = 0 ⇒ 0/0 = nan) — the paper's
    # documented |grad|<Θ failure mode, same behavior AD gives. Padded
    # columns emit 0 (x = 0, sin 0 = 0) and are sliced off by ops.py.
    g = (4.0 * e1 / (d * s1))[:, None] * x + (
        (2.0 * jnp.pi / d) * jnp.sin(two_pi_x)) * e2[:, None]
    return f, g


# name -> factory(true_dim) -> body(x, *, with_grad) -> (f, g | None).
# Padding-exact bodies ignore the dim.
OBJECTIVE_BODIES = {
    "rastrigin": lambda d: _rastrigin_body,
    "sphere": lambda d: _sphere_body,
    "rosenbrock": lambda d: _rosenbrock_body,
    "ackley": lambda d: functools.partial(_ackley_body, d=d),
}


def objective_body(name: str, dim: int):
    """The in-kernel row-wise body for `name` with the true dim baked in.

    Returns `body(x (N, Dp), *, with_grad=False) -> (f (N,), g (N, Dp) |
    None)`. Row-independent by contract (row i depends only on row i), so
    callers may stack any number of rows — the property every exact-parity
    schedule in the engine leans on."""
    return OBJECTIVE_BODIES[name](dim)


# f leaves the kernels as an (Np, 1) column blocked (tn, 1): Mosaic keeps
# the row reduction's result in that layout, and a 1-D (tn,) block of an
# (Np,) array is refused for every Np > tn (XLA tiles the array T(1024)).
def _value_kernel(body, x_ref, f_ref):
    f, _ = body(x_ref[...])
    f_ref[...] = f[:, None].astype(f_ref.dtype)


def _value_grad_kernel(body, x_ref, f_ref, g_ref):
    f, g = body(x_ref[...], with_grad=True)
    f_ref[...] = f[:, None].astype(f_ref.dtype)
    g_ref[...] = g.astype(g_ref.dtype)


def fused_value_pallas(name: str, x: jnp.ndarray, *, dim: int = None,
                       particle_tile: int = 256, interpret=False):
    """x (N, D) -> f (N,): batched objective values in one pass. `dim` is
    the true lane dim when x arrives zero-padded (defaults to x's)."""
    N, D = x.shape
    body = objective_body(name, dim if dim is not None else D)
    tn = min(particle_tile, N)
    Np = ((N + tn - 1) // tn) * tn
    if Np != N:
        x = jnp.pad(x, ((0, Np - N), (0, 0)))
    f = pl.pallas_call(
        functools.partial(_value_kernel, body),
        grid=(Np // tn,),
        in_specs=[pl.BlockSpec((tn, D), lambda n: (n, 0))],
        out_specs=pl.BlockSpec((tn, 1), lambda n: (n, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, 1), x.dtype),
        interpret=interpret,
    )(x)
    return f[:N, 0]


def fused_value_grad_pallas(name: str, x: jnp.ndarray, *, dim: int = None,
                            particle_tile: int = 256, interpret=False):
    """x (N, D) -> (f (N,), g (N, D)) in one fused pass. `dim` is the true
    lane dim when x arrives zero-padded (defaults to x's)."""
    N, D = x.shape
    body = objective_body(name, dim if dim is not None else D)
    tn = min(particle_tile, N)
    # Pad the particle axis up to a tile multiple instead of shrinking the
    # tile to whatever divides N (degrades to tile=1 for prime N). Padded
    # rows are all-zero particles: every body here is row-independent, so
    # they compute garbage rows that are sliced off below — exact.
    Np = ((N + tn - 1) // tn) * tn
    if Np != N:
        x = jnp.pad(x, ((0, Np - N), (0, 0)))
    f, g = pl.pallas_call(
        functools.partial(_value_grad_kernel, body),
        grid=(Np // tn,),
        in_specs=[pl.BlockSpec((tn, D), lambda n: (n, 0))],
        out_specs=[
            pl.BlockSpec((tn, 1), lambda n: (n, 0)),
            pl.BlockSpec((tn, D), lambda n: (n, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, 1), x.dtype),
            jax.ShapeDtypeStruct((Np, D), x.dtype),
        ],
        interpret=interpret,
    )(x)
    return f[:N, 0], g[:N]
