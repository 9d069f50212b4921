"""Sweep megakernel (engine sweep_mode="megakernel", ISSUE 6).

The megakernel contract is EXACT — no tolerance. The fused sweep kernel
reproduces the staged batched program's reduction shapes (one lane per grid
step, the staged update kernel's (Dp, Dp)×(Dp, 1) dots, curvature on the
true-D slice) and its materialization seams (optimization_barriers at the
staged pallas_call boundaries), so trajectories, accepted α (visible
through x), statuses, and all counters must be ARRAY-EQUAL to
sweep_mode="batched" across fused objectives × lane_chunk × ladder_len ×
compact/repack/auto schedules.

Legs: on CPU these tests exercise the REAL kernel bodies through Pallas
interpret mode (the default off-TPU dispatch); the REPRO_DISABLE_PALLAS=1
leg checks the other dispatch arm, where the megakernel step delegates
wholesale to the staged step (trivially exact by construction — the test
pins the routing, not the arithmetic).

Unsupported configurations (no analytic fused body, no dense-H strategy,
rosenbrock at non-128-multiple D, oversized D·D tiles) must fall back to
the staged path with a RuntimeWarning and identical results.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.core import BFGSOptions, LBFGSOptions, batched_bfgs, batched_lbfgs
from repro.core.objectives import get_objective


def _starts(name, B, dim, seed):
    obj = get_objective(name)
    return obj, jax.random.uniform(jax.random.key(seed), (B, dim),
                                   minval=obj.lower, maxval=obj.upper)


def _frozen_mix(frozen_mask, dim=3, seed=3):
    """(B, dim) rastrigin starts: True rows at the origin — where rastrigin's
    gradient 2x + 20π·sin(2πx) is bit-exact zero, so the lane is
    converged-from-init at any theta — False rows at a fixed random start
    that never reaches theta=1e-30. Deterministic freeze patterns on a
    megakernel-supported objective (the PR-4 harness used rosenbrock at
    D=2, which the megakernel routes back to the staged path)."""
    frozen_mask = np.asarray(frozen_mask, bool)
    x0 = np.array(jax.random.uniform(
        jax.random.key(seed), (frozen_mask.shape[0], dim),
        minval=1.0, maxval=3.0))  # np.array: jax buffers are read-only
    x0[frozen_mask] = 0.0
    return jnp.asarray(x0, jnp.float32)


def _assert_exact(ref, mega):
    for fld in ("x", "fval", "grad_norm", "status", "n_evals", "eval_rows",
                "map_trips"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, fld)), np.asarray(getattr(mega, fld)),
            err_msg=fld)
    assert int(ref.iterations) == int(mega.iterations)
    assert int(ref.n_converged) == int(mega.n_converged)


def _pair(f, x0, **kw):
    base = dict(iter_bfgs=kw.pop("iter_bfgs", 30),
                theta=kw.pop("theta", 1e-4),
                ad_mode=kw.pop("ad_mode", "reverse"), **kw)
    ref = batched_bfgs(f, x0, BFGSOptions(sweep_mode="batched", **base))
    mega = batched_bfgs(f, x0, BFGSOptions(sweep_mode="megakernel", **base))
    return ref, mega


class TestMegakernelParity:
    """Array-equal vs the staged batched path, both Pallas-dispatch legs."""

    @pytest.mark.parametrize("name,dim", [
        ("sphere", 4), ("rastrigin", 3), ("ackley", 3), ("rastrigin", 10)])
    def test_full_ladder_exact(self, name, dim):
        """ladder_len=0: the ONE-launch fused path on every fused objective
        (rosenbrock needs 128-aligned D — covered separately)."""
        obj, x0 = _starts(name, 13, dim, seed=dim)
        _assert_exact(*_pair(obj.fn, x0))

    def test_rosenbrock_aligned_dim(self):
        """rosenbrock IS megakernel-eligible when no lane padding is needed
        (Dp == D): the one fused-objective case whose padding rule is
        dimension-dependent."""
        obj, x0 = _starts("rosenbrock", 4, 128, seed=0)
        _assert_exact(*_pair(obj.fn, x0, iter_bfgs=8))

    @pytest.mark.parametrize("ladder", [2, 4, 19])
    def test_adaptive_ladder_exact(self, ladder):
        """0 < ladder_len < ls_iters: staged speculative launch + fallback
        probes verbatim, then the fused commit kernel (launch #2)."""
        obj, x0 = _starts("rastrigin", 13, 3, seed=1)
        _assert_exact(*_pair(obj.fn, x0, ladder_len=ladder))

    def test_ladder_at_least_ls_iters_is_full_path(self):
        """ladder_len >= ls_iters collapses to the full ladder — the
        one-launch kernel, not the commit split."""
        obj, x0 = _starts("rastrigin", 9, 3, seed=2)
        _assert_exact(*_pair(obj.fn, x0, ladder_len=25, ls_iters=20))

    def test_lane_chunk_exact(self):
        obj, x0 = _starts("ackley", 14, 3, seed=4)  # 14 = uneven tail chunk
        _assert_exact(*_pair(obj.fn, x0, lane_chunk=4))

    def test_composes_with_compaction(self):
        obj, x0 = _starts("rastrigin", 16, 3, seed=5)
        _assert_exact(*_pair(obj.fn, x0, compact_every=1))

    def test_composes_with_repack_and_compact(self):
        obj, x0 = _starts("rastrigin", 16, 3, seed=6)
        _assert_exact(*_pair(obj.fn, x0, lane_chunk=4, repack_every=2,
                             compact_every=1))

    def test_composes_with_auto_schedule(self):
        """The auto controller's step_L closures pick the megakernel step:
        plans, schedule_trace, and the replayed trajectory stay identical."""
        obj, x0 = _starts("ackley", 12, 3, seed=7)
        ref, mega = _pair(obj.fn, x0, schedule="auto", schedule_every=2)
        _assert_exact(ref, mega)
        np.testing.assert_array_equal(np.asarray(ref.schedule_trace),
                                      np.asarray(mega.schedule_trace))

    def test_frozen_lanes_stay_frozen(self):
        """Mixed frozen/active stacks: kernel-side ok-masking (ρ = 0 ⇒
        H' = H) plus engine keep-masking reproduce the staged freeze."""
        x0 = _frozen_mix([True] * 9 + [False] * 7)
        _assert_exact(*_pair(get_objective("rastrigin").fn, x0,
                             theta=1e-30, iter_bfgs=6, ls_iters=8))

    def test_disable_pallas_ref_leg(self, monkeypatch):
        """REPRO_DISABLE_PALLAS=1: the megakernel step must delegate to the
        staged step (its reference semantics) — trivially identical."""
        monkeypatch.setenv("REPRO_DISABLE_PALLAS", "1")
        obj, x0 = _starts("rastrigin", 12, 3, seed=8)
        _assert_exact(*_pair(obj.fn, x0, ladder_len=4))


class TestMegakernelFallback:
    """Unsupported configs: staged path + RuntimeWarning, identical results."""

    def _expect_fallback(self, f, x0, match, **kw):
        base = {"iter_bfgs": 20, "theta": 1e-4, "ad_mode": "reverse", **kw}
        ref = batched_bfgs(f, x0, BFGSOptions(sweep_mode="batched", **base))
        with pytest.warns(RuntimeWarning, match=match):
            mega = batched_bfgs(f, x0,
                                BFGSOptions(sweep_mode="megakernel", **base))
        _assert_exact(ref, mega)

    def test_rosenbrock_unaligned_dim(self):
        """Lane padding is inexact for rosenbrock's coupled terms, so
        D = 5 must route back to the staged path."""
        obj, x0 = _starts("rosenbrock", 8, 5, seed=0)
        self._expect_fallback(obj.fn, x0, match="rosenbrock")

    def test_non_fused_objective(self):
        """A bare callable has no analytic fused body to inline."""
        _, x0 = _starts("sphere", 8, 3, seed=1)
        self._expect_fallback(lambda x: jnp.sum(x * x), x0,
                              match="analytic")

    def test_non_dense_strategy(self):
        """L-BFGS has no dense H tile to keep resident: megakernel falls
        back to the staged batched path for its vmapped adapter."""
        obj, x0 = _starts("sphere", 8, 3, seed=2)
        base = dict(iter_max=20, theta=1e-4)
        ref = batched_lbfgs(obj.fn, x0,
                            LBFGSOptions(sweep_mode="batched", **base))
        with pytest.warns(RuntimeWarning, match="dense-H"):
            mega = batched_lbfgs(
                obj.fn, x0, LBFGSOptions(sweep_mode="megakernel", **base))
        _assert_exact(ref, mega)

    def test_oversized_dim(self, monkeypatch):
        """D·D tiles past the VMEM cap route back to the staged path. The
        cap is monkeypatched down so the test doesn't allocate a real
        >1024² H stack."""
        from repro.kernels import ops as kernel_ops
        monkeypatch.setattr(kernel_ops, "MEGAKERNEL_MAX_DIM", 128)
        obj, x0 = _starts("rastrigin", 6, 130, seed=3)  # pads to 256 > 128
        self._expect_fallback(obj.fn, x0, match=r"Dp=256, cap 128\).*VMEM",
                              iter_bfgs=4)

    def test_unknown_sweep_mode_message(self):
        obj, x0 = _starts("sphere", 4, 2, seed=0)
        with pytest.raises(ValueError, match="megakernel"):
            batched_bfgs(obj.fn, x0, BFGSOptions(sweep_mode="bogus"))

    def test_wolfe_rejected(self):
        obj, x0 = _starts("sphere", 4, 2, seed=0)
        with pytest.raises(ValueError, match="armijo"):
            batched_bfgs(obj.fn, x0, BFGSOptions(sweep_mode="megakernel",
                                                 linesearch="wolfe"))


class TestMegakernelCounters:
    """The megakernel changes launches, not rows: eval accounting and the
    rung histogram signal must be untouched (the auto controller's inputs)."""

    def test_rows_match_staged_under_freeze(self):
        B, S, K = 16, 4, 8
        x0 = _frozen_mix([True] * 12 + [False] * 4)
        base = dict(iter_bfgs=S, theta=1e-30, ls_iters=K, ad_mode="reverse")
        ref = batched_bfgs(get_objective("rastrigin").fn, x0,
                           BFGSOptions(sweep_mode="batched", **base))
        mega = batched_bfgs(get_objective("rastrigin").fn, x0,
                            BFGSOptions(sweep_mode="megakernel", **base))
        _assert_exact(ref, mega)
        assert int(mega.iterations) == S
        # full ladder: init row per lane + (K probes + 1 vg) per lane-sweep
        assert int(mega.eval_rows) == B + S * B * (K + 1)

    def test_compacted_megakernel_rows_shrink(self):
        """Compaction composes: the fused kernel runs on the gathered
        active-prefix buckets, so frozen-tail rows drop exactly as staged."""
        S, K = 4, 8
        x0 = _frozen_mix([True] * 12 + [False] * 4)
        base = dict(iter_bfgs=S, theta=1e-30, ls_iters=K, ad_mode="reverse",
                    sweep_mode="megakernel")
        full = batched_bfgs(get_objective("rastrigin").fn, x0,
                            BFGSOptions(**base))
        comp = batched_bfgs(get_objective("rastrigin").fn, x0,
                            BFGSOptions(compact_every=1, **base))
        for fld in ("x", "fval", "grad_norm", "status", "n_evals"):
            np.testing.assert_array_equal(
                np.asarray(getattr(full, fld)), np.asarray(getattr(comp, fld)),
                err_msg=fld)
        assert int(comp.eval_rows) < int(full.eval_rows)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
class TestMegakernelProperty:
    """Randomized freeze patterns × ladder lengths through the same exact
    assertion — the PR-4 harness shape on the megakernel-supported mix."""

    @given(
        frozen=st.lists(st.booleans(), min_size=6, max_size=12),
        ladder=st.sampled_from([0, 2, 5]),
        chunked=st.booleans(),
    )
    @settings(max_examples=8, deadline=None)
    def test_random_freeze_patterns(self, frozen, ladder, chunked):
        if not any(not fz for fz in frozen):
            frozen[0] = False  # keep at least one active lane
        x0 = _frozen_mix(frozen)
        kw = dict(theta=1e-30, iter_bfgs=4, ls_iters=6, ladder_len=ladder)
        if chunked:
            kw["lane_chunk"] = 4
        _assert_exact(*_pair(get_objective("rastrigin").fn, x0, **kw))
