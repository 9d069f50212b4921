"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.bfgs import hessian_update_fast, hessian_update_reference
from repro.core.linesearch import armijo_backtracking
from repro.core.objectives import rastrigin, rosenbrock, sphere
from repro.sharding import logical_to_spec, make_mesh

_dims = st.integers(2, 12)
_seeds = st.integers(0, 2**31 - 1)


@settings(max_examples=25, deadline=None)
@given(_dims, _seeds)
def test_armijo_condition_holds_at_returned_alpha(dim, seed):
    """Invariant (Alg. 6): the accepted step satisfies
    f(x + αp) <= f(x) + c1·α·(∇f·p) whenever p is a descent direction."""
    key = jax.random.key(seed)
    x = jax.random.uniform(key, (dim,), minval=-3, maxval=3)
    f = sphere
    g = jax.grad(f)(x)
    p = -g  # steepest descent: guaranteed descent direction
    f0 = f(x)
    res = armijo_backtracking(f, x, p, f0, g, c1=0.3, max_iters=20)
    lhs = float(f(x + res.alpha * p))
    rhs = float(f0 + 0.3 * res.alpha * jnp.dot(g, p))
    assert lhs <= rhs + 1e-5 * max(1.0, abs(rhs))


@settings(max_examples=25, deadline=None)
@given(_dims, _seeds)
def test_bfgs_update_preserves_spd(dim, seed):
    """Invariant: with positive curvature (δxᵀδg > 0), the BFGS update maps
    SPD H to SPD H' (both algebraic forms)."""
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    A = jax.random.normal(k1, (dim, dim))
    H = A @ A.T / dim + 2.0 * jnp.eye(dim)
    dx = jax.random.normal(k2, (dim,))
    dg = 0.7 * dx + 0.1 * jax.random.normal(k3, (dim,))
    if float(jnp.dot(dx, dg)) <= 1e-6:
        return  # curvature condition not met; update is skipped in core
    for fn in (hessian_update_reference, hessian_update_fast):
        Hn = np.asarray(fn(H, dx, dg), np.float64)
        Hn = 0.5 * (Hn + Hn.T)
        eig = np.linalg.eigvalsh(Hn)
        assert eig.min() > -1e-4 * max(1.0, eig.max()), eig.min()


@settings(max_examples=25, deadline=None)
@given(_dims, _seeds)
def test_secant_equation(dim, seed):
    """Invariant: H' δg = δx (the defining quasi-Newton property)."""
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    A = jax.random.normal(k1, (dim, dim))
    H = A @ A.T / dim + 2.0 * jnp.eye(dim)
    dx = jax.random.normal(k2, (dim,))
    dg = 0.7 * dx + 0.1 * jax.random.normal(k3, (dim,))
    if abs(float(jnp.dot(dx, dg))) <= 1e-4:
        return
    Hn = hessian_update_fast(H, dx, dg)
    np.testing.assert_allclose(
        np.asarray(Hn @ dg, np.float64), np.asarray(dx, np.float64),
        rtol=2e-3, atol=2e-3)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 4), st.integers(1, 4))
def test_sharding_spec_never_reuses_mesh_axes(seed, d1, d2):
    """Invariant: one mesh axis shards at most one dim of any array."""
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = np.random.default_rng(seed)
    names = ["batch", "heads", "mlp", "fsdp", "expert", "vocab", None,
             "embed", "kv_heads", "expert_mlp"]
    axes = tuple(rng.choice(names) for _ in range(d1 + d2))
    shape = tuple(int(rng.choice([1, 2, 8, 16, 64])) for _ in range(d1 + d2))
    spec = logical_to_spec(mesh, axes, shape)
    flat = []
    for part in spec:
        if part is None:
            continue
        flat.extend(part if isinstance(part, tuple) else (part,))
    assert len(flat) == len(set(flat)), (axes, shape, spec)


@settings(max_examples=15, deadline=None)
@given(_seeds)
def test_lm_loss_matches_manual_cross_entropy(seed):
    from repro.train.step import lm_loss
    key = jax.random.key(seed)
    B, S, V = 2, 5, 11
    logits = jax.random.normal(key, (B, S, V))
    labels = jax.random.randint(jax.random.key(seed + 1), (B, S), 0, V)
    mask = jnp.ones((B, S))
    got = float(lm_loss(logits, labels, mask, z_loss=0.0))
    p = jax.nn.log_softmax(logits, axis=-1)
    want = float(-jnp.mean(
        jnp.take_along_axis(p, labels[..., None], axis=-1)))
    assert got == pytest.approx(want, rel=1e-5)


@settings(max_examples=10, deadline=None)
@given(_seeds, st.integers(2, 5))
def test_chunked_ssd_engine_matches_naive_recurrence(seed, heads):
    """Invariant: the chunked linear-recurrence engine equals the naive
    sequential recurrence h_t = a_t h_{t-1} + i_t v_t k_tᵀ, y_t = q_t h_t."""
    from repro.models.mamba import chunked_linear_recurrence
    key = jax.random.key(seed)
    B, L, H, P, N = 1, 12, heads, 4, 3
    ks = jax.random.split(key, 5)
    v = jax.random.normal(ks[0], (B, L, H, P))
    k = jax.random.normal(ks[1], (B, L, H, N))
    q = jax.random.normal(ks[2], (B, L, H, N))
    log_a = -jax.random.uniform(ks[3], (B, L, H), minval=0.01, maxval=1.0)
    gi = jax.random.uniform(ks[4], (B, L, H), minval=0.1, maxval=1.0)

    y_chunked, h_fin = chunked_linear_recurrence(v, k, q, log_a, gi, chunk=4)

    h = np.zeros((B, H, P, N))
    ys = []
    for t in range(L):
        a = np.exp(np.asarray(log_a[:, t], np.float64))[..., None, None]
        h = a * h + np.asarray(gi[:, t], np.float64)[..., None, None] * (
            np.asarray(v[:, t], np.float64)[..., None]
            * np.asarray(k[:, t], np.float64)[..., None, :, ])
        ys.append(np.einsum("bhn,bhpn->bhp", np.asarray(q[:, t], np.float64), h))
    y_naive = np.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunked, np.float64), y_naive,
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(h_fin, np.float64), h,
                               rtol=1e-3, atol=1e-3)
