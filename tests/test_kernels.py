"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Per the assignment: shape/dtype sweeps + hypothesis property tests, with
assert_allclose against ref.py for every kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.bfgs_update import LANE_MINOR_MAX_DIM


def _spd_hessians(key, B, D, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    A = jax.random.normal(k1, (B, D, D), jnp.float32)
    H = jnp.einsum("bij,bkj->bik", A, A) / D + 2.0 * jnp.eye(D)
    dx = jax.random.normal(k2, (B, D), jnp.float32)
    dg = 0.5 * dx + 0.2 * jax.random.normal(k3, (B, D), jnp.float32)
    return H.astype(dtype), dx.astype(dtype), dg.astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.float64: dict(rtol=1e-9, atol=1e-9)}


class TestBFGSUpdateKernel:
    @pytest.mark.parametrize("B", [1, 3, 8])
    @pytest.mark.parametrize("D", [2, 5, 16, 130])
    def test_shape_sweep(self, B, D):
        H, dx, dg = _spd_hessians(jax.random.key(B * 131 + D), B, D, jnp.float32)
        out = ops.bfgs_update(H, dx, dg)
        expect = ref.bfgs_update_ref(H, dx, dg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   **TOL[jnp.float32])

    def test_fused_update_direction(self):
        H, dx, dg = _spd_hessians(jax.random.key(0), 4, 12, jnp.float32)
        g = jax.random.normal(jax.random.key(9), (4, 12))
        Hn, p = ops.bfgs_update_direction(H, dx, dg, g)
        Hr, pr = ref.update_direction_ref(H, dx, dg, g)
        np.testing.assert_allclose(np.asarray(Hn), np.asarray(Hr), rtol=3e-4,
                                   atol=3e-4)
        np.testing.assert_allclose(np.asarray(p), np.asarray(pr), rtol=3e-4,
                                   atol=3e-4)

    @pytest.mark.parametrize("B,D", [(3, 8), (5, 130)])
    def test_guarded_update_direction(self, B, D):
        """The batched sweep's guarded fused pass: ρ in, (H', p') out."""
        H, dx, dg = _spd_hessians(jax.random.key(B + D), B, D, jnp.float32)
        gn = jax.random.normal(jax.random.key(2), (B, D))
        rho = 1.0 / jnp.sum(dx * dg, axis=-1)
        Hn, p = ops.guarded_update_direction(H, dx, dg, gn, rho)
        Hr, pr = ref.guarded_update_direction_ref(H, dx, dg, gn, rho)
        np.testing.assert_allclose(np.asarray(Hn), np.asarray(Hr),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(p), np.asarray(pr),
                                   rtol=3e-4, atol=2e-3)

    @pytest.mark.parametrize("D", [12, 130])  # lane-minor, per-lane MXU
    def test_guarded_rho_zero_keeps_h_exactly(self, D):
        """ρ = 0 with zeroed pairs must leave H bitwise unchanged and emit
        p = -H g — that is how the engine's curvature guard and frozen-lane
        masking lift into the kernel with no second read of H."""
        H, dx, dg = _spd_hessians(jax.random.key(7), 3, D, jnp.float32)
        gn = jax.random.normal(jax.random.key(8), (3, D))
        rho = (1.0 / jnp.sum(dx * dg, axis=-1)).at[1].set(0.0)
        dx = dx.at[1].set(0.0)
        dg = dg.at[1].set(0.0)
        Hn, p = ops.guarded_update_direction(H, dx, dg, gn, rho)
        np.testing.assert_array_equal(np.asarray(Hn[1]), np.asarray(H[1]))
        np.testing.assert_allclose(np.asarray(p[1]),
                                   np.asarray(-(H[1] @ gn[1])),
                                   rtol=2e-4, atol=2e-4)

    def test_preserves_symmetry_and_secant(self):
        """BFGS invariants: H' symmetric; secant H' δg = δx."""
        H, dx, dg = _spd_hessians(jax.random.key(3), 2, 8, jnp.float32)
        out = np.asarray(ops.bfgs_update(H, dx, dg), np.float64)
        np.testing.assert_allclose(out, out.transpose(0, 2, 1), atol=1e-3)
        lhs = np.einsum("bij,bj->bi", out, np.asarray(dg, np.float64))
        np.testing.assert_allclose(lhs, np.asarray(dx, np.float64),
                                   rtol=2e-3, atol=2e-3)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 4), st.integers(2, 24), st.integers(0, 2**31 - 1))
    def test_property_matches_reference(self, B, D, seed):
        H, dx, dg = _spd_hessians(jax.random.key(seed), B, D, jnp.float32)
        out = ops.bfgs_update(H, dx, dg)
        expect = ref.bfgs_update_ref(H, dx, dg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=5e-3, atol=5e-3)


def _guarded_args(B, D, seed):
    H, dx, dg = _spd_hessians(jax.random.key(seed), B, D, jnp.float32)
    gn = jax.random.normal(jax.random.key(seed + 1), (B, D))
    return H, dx, dg, gn, 1.0 / jnp.sum(dx * dg, axis=-1)


def _pallas_out_shapes(B, D):
    """Output shapes of the Pallas call ops.guarded_update_direction makes."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32)
            for s in ((B, D, D), (B, D), (B, D), (B, D), (B,))]
    eqns = [e for e in jax.make_jaxpr(ops.guarded_update_direction)(
        *args).eqns if e.primitive.name == "pallas_call"]
    assert len(eqns) == 1
    return [tuple(v.aval.shape) for v in eqns[0].outvars]


class TestLaneMinorGuardedUpdate:
    """Up to LANE_MINOR_MAX_DIM the guarded update runs with lanes on the
    minor axis and D unpadded (bfgs_update.update_direction_rows)."""

    @pytest.mark.parametrize("B", [1, 7, 128, 300])  # 300: lane padding
    @pytest.mark.parametrize("D", [1, 2, 10, LANE_MINOR_MAX_DIM])
    def test_matches_reference(self, B, D):
        args = _guarded_args(B, D, seed=B * 131 + D)
        Hn, p = ops.guarded_update_direction(*args)
        Hr, pr = ref.guarded_update_direction_ref(*args)
        assert Hn.shape == (B, D, D) and p.shape == (B, D)
        np.testing.assert_allclose(np.asarray(Hn), np.asarray(Hr),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(p), np.asarray(pr),
                                   rtol=3e-4, atol=2e-3)

    @pytest.mark.parametrize("D", [3, 10])
    def test_lane_independent_of_batch(self, D):
        """A lane's H', p' are array-equal whether it rides alone, in a
        batch of 7 (a 128-lane tile) or of 300 (a 384-lane tile):
        compaction's contract."""
        args = _guarded_args(300, D, seed=D)
        Hn, p = ops.guarded_update_direction(*args)
        for b in (1, 7):
            Hb, pb = ops.guarded_update_direction(*(a[:b] for a in args))
            np.testing.assert_array_equal(np.asarray(Hb), np.asarray(Hn[:b]))
            np.testing.assert_array_equal(np.asarray(pb), np.asarray(p[:b]))

    @pytest.mark.parametrize("B,D,shapes", [
        (8, 10, [(10, 10, 128), (10, 128)]),  # lanes minor, padded to 128
        (8, 128, [(8, 128, 128), (8, 1, 128)]),  # one lane per grid step
        (8, LANE_MINOR_MAX_DIM + 1, [(8, 128, 128), (8, 1, 128)]),
    ])
    def test_layout_is_chosen_by_dim(self, B, D, shapes):
        assert _pallas_out_shapes(B, D) == shapes


class TestDirectionKernel:
    @pytest.mark.parametrize("B,D", [(1, 4), (8, 16), (5, 129)])
    def test_matches_ref(self, B, D):
        key = jax.random.key(B + D)
        H = jax.random.normal(key, (B, D, D))
        g = jax.random.normal(jax.random.key(1), (B, D))
        np.testing.assert_allclose(
            np.asarray(ops.direction(H, g)),
            np.asarray(ref.direction_ref(H, g)),
            rtol=2e-4, atol=2e-4)


class TestPSOStepKernel:
    @pytest.mark.parametrize("N,D", [(4, 2), (64, 5), (257, 10)])
    def test_matches_ref(self, N, D):
        ks = jax.random.split(jax.random.key(N * D), 6)
        x, v, px = (jax.random.normal(k, (N, D)) for k in ks[:3])
        gx = jax.random.normal(ks[3], (D,))
        r1, r2 = (jax.random.uniform(k, (N, D)) for k in ks[4:])
        xn, vn = ops.pso_step_update(x, v, px, gx, r1, r2, 0.5, 1.2, 1.5)
        xr, vr = ref.pso_step_ref(x, v, px, gx, r1, r2, 0.5, 1.2, 1.5)
        np.testing.assert_allclose(np.asarray(xn), np.asarray(xr), rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(vn), np.asarray(vr), rtol=1e-5,
                                   atol=1e-5)


class TestFusedObjectiveKernels:
    @pytest.mark.parametrize("name", ops.FUSED_OBJECTIVES)
    @pytest.mark.parametrize("N,D", [(8, 2), (32, 5), (16, 128)])
    def test_matches_ref_and_canonical(self, name, N, D):
        from repro.core import objectives as OB
        x = jax.random.uniform(jax.random.key(D), (N, D), minval=-4, maxval=4)
        f_k, g_k = ops.fused_value_grad(name, x)
        f_r, g_r = getattr(ref, f"{name}_vg_ref")(x)
        np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_r),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_r),
                                   rtol=1e-4, atol=1e-4)
        # and the ref against jax.grad of the canonical scalar objective
        g_canon = jax.vmap(jax.grad(getattr(OB, name)))(x)
        np.testing.assert_allclose(np.asarray(g_r), np.asarray(g_canon),
                                   rtol=1e-3, atol=1e-3)

    def test_rastrigin_padding_exact(self):
        """Zero padding must be exact for rastrigin (cos(0) cancellation)."""
        x = jax.random.uniform(jax.random.key(0), (4, 7), minval=-5, maxval=5)
        f_k, _ = ops.fused_value_grad("rastrigin", x)
        f_direct = ref.rastrigin_vg_ref(x)[0]
        np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_direct),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("name", ops.FUSED_OBJECTIVES)
    @pytest.mark.parametrize("N", [8, 251])  # 251 exercises particle padding
    def test_value_only_twin_bitwise_consistent(self, name, N):
        """fused_value must agree with fused_value_grad's f to fp rounding:
        the speculative Armijo compares the two against each other."""
        x = jax.random.uniform(jax.random.key(N), (N, 6), minval=-4, maxval=4)
        f_v = ops.fused_value(name, x)
        f_vg, _ = ops.fused_value_grad(name, x)
        np.testing.assert_array_equal(np.asarray(f_v), np.asarray(f_vg))

    @pytest.mark.parametrize("name", ops.FUSED_OBJECTIVES)
    def test_prime_particle_count_padded_not_degraded(self, name):
        """Prime N previously degraded the particle tile to 1; rows are now
        padded to the tile multiple and the outputs sliced — exact."""
        x = jax.random.uniform(jax.random.key(1), (257, 5), minval=-4,
                               maxval=4)
        f_k, g_k = ops.fused_value_grad(name, x)
        f_r, g_r = getattr(ref, f"{name}_vg_ref")(x)
        assert f_k.shape == (257,) and g_k.shape == (257, 5)
        np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_r),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_r),
                                   rtol=1e-4, atol=1e-4)


def test_kernels_disabled_env(monkeypatch):
    monkeypatch.setenv("REPRO_DISABLE_PALLAS", "1")
    H, dx, dg = _spd_hessians(jax.random.key(1), 2, 4, jnp.float32)
    out = ops.bfgs_update(H, dx, dg)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.bfgs_update_ref(H, dx, dg)),
                               rtol=1e-6)


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("B,S,H,KV,hd,bq,bk,causal", [
        (1, 128, 2, 2, 16, 64, 64, True),
        (2, 256, 4, 2, 32, 128, 64, True),
        (1, 128, 4, 1, 16, 32, 128, False),
        (2, 64, 8, 4, 64, 64, 32, True),
    ])
    def test_matches_ref(self, B, S, H, KV, hd, bq, bk, causal):
        ks = jax.random.split(jax.random.key(B * S + H), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd))
        k = jax.random.normal(ks[1], (B, S, KV, hd))
        v = jax.random.normal(ks[2], (B, S, KV, hd))
        out = ops.flash_attention(q, k, v, causal=causal,
                                  block_q=bq, block_k=bk)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_matches_model_attention_path(self):
        """The kernel agrees with the LM substrate's chunked-jnp attention."""
        from repro.models import attention as A
        from repro.configs import get_config, reduce_config
        cfg = reduce_config(get_config("phi3-mini-3.8b"))
        B, S, H, hd = 2, 64, 4, 16
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd))
        k = jax.random.normal(ks[1], (B, S, H, hd))
        v = jax.random.normal(ks[2], (B, S, H, hd))
        pos = jnp.arange(S)
        want = A._direct_attention(q, k, v, pos, pos, cfg, True, 0)
        got = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.sampled_from([1, 2, 4]),
           st.booleans())
    def test_property_random_gqa(self, seed, g, causal):
        ks = jax.random.split(jax.random.key(seed), 3)
        B, S, KV, hd = 1, 64, 2, 16
        H = KV * g
        q = jax.random.normal(ks[0], (B, S, H, hd))
        k = jax.random.normal(ks[1], (B, S, KV, hd))
        v = jax.random.normal(ks[2], (B, S, KV, hd))
        out = ops.flash_attention(q, k, v, causal=causal,
                                  block_q=32, block_k=32)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)
