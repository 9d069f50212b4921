"""Fault-tolerance suite (DESIGN.md §15): checkpoint/resume exactness, lane
quarantine + retry, deterministic fault injection, preemption, StepGuard.

The load-bearing contract is ARRAY-EQUALITY, not tolerance: a solve that is
preempted mid-flight and resumed from its newest COMMITted snapshot must
reproduce the uninterrupted solve bit for bit — trajectories, statuses,
eval_rows, map_trips and the schedule trace, with no double-counting of the
replayed sweeps. That holds because the engine's while-loop carry
(EngineCarry) contains every mutable datum: lanes, dense-H stacks, gather
plans, the auto-scheduling controller, PRNG retry streams and all counters.
"""
import dataclasses
import os
import shutil
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bfgs import BFGSOptions, batched_bfgs
from repro.core.engine import CONVERGED, DIVERGED
from repro.core.lbfgs import LBFGSOptions, batched_lbfgs
from repro.core.objectives import ackley, rosenbrock
from repro.core.pso import PSOOptions
from repro.core.zeus import ZeusOptions, zeus
from repro.launch.faults import (FaultPlan, Preempted, StepGuard,
                                 injection_masks)

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _x0(n=10, d=3, seed=0, lo=-2.0, hi=2.0):
    return jax.random.uniform(jax.random.key(seed), (n, d), jnp.float32,
                              lo, hi)


def _assert_result_equal(a, b, skip=()):
    """Array-equality over every BFGSResult field (None-ness included)."""
    for fld in a._fields:
        if fld in skip:
            continue
        va, vb = getattr(a, fld), getattr(b, fld)
        assert (va is None) == (vb is None), fld
        if va is not None:
            np.testing.assert_array_equal(
                np.asarray(va), np.asarray(vb), err_msg=fld)


# ---------------------------------------------------------------------------
# StepGuard: one slow step skips at most ONE subsequent step
# ---------------------------------------------------------------------------
class TestStepGuard:
    def test_breach_skips_exactly_once(self):
        g = StepGuard(deadline_s=1e-9, on_breach="skip")
        with g.step(0):
            pass  # any wall time exceeds a 1ns deadline
        assert g.breaches == 1
        assert g.should_skip_next() is True
        # pre-fix behavior: this stayed True forever after one breach
        assert g.should_skip_next() is False
        assert g.should_skip_next() is False

    def test_rearms_on_next_breach(self):
        g = StepGuard(deadline_s=1e-9, on_breach="skip")
        for i in range(2):
            with g.step(i):
                pass
            assert g.should_skip_next() is True
            assert g.should_skip_next() is False
        assert g.breaches == 2

    def test_warn_policy_never_skips(self):
        g = StepGuard(deadline_s=1e-9, on_breach="warn")
        with g.step(0):
            pass
        assert g.breaches == 1
        assert g.should_skip_next() is False

    def test_abort_policy_raises(self):
        g = StepGuard(deadline_s=1e-9, on_breach="abort")
        with pytest.raises(TimeoutError):
            with g.step(0):
                pass

    def test_no_deadline_never_breaches(self):
        g = StepGuard(deadline_s=0.0, on_breach="skip")
        with g.step(0):
            pass
        assert g.breaches == 0 and g.should_skip_next() is False


# ---------------------------------------------------------------------------
# FaultPlan: deterministic, hashable, validated
# ---------------------------------------------------------------------------
class TestFaultPlan:
    def test_random_is_deterministic(self):
        a = FaultPlan.random(7, n_sweeps=20, n_lanes=8, n_nan=3, n_kill=2,
                             preempt_at_sweep=11)
        b = FaultPlan.random(7, n_sweeps=20, n_lanes=8, n_nan=3, n_kill=2,
                             preempt_at_sweep=11)
        assert a == b and hash(a) == hash(b)
        c = FaultPlan.random(8, n_sweeps=20, n_lanes=8, n_nan=3, n_kill=2)
        assert a != c

    def test_validation(self):
        with pytest.raises(ValueError):
            FaultPlan(nan_grads=((-1, 0),))
        with pytest.raises(ValueError):
            FaultPlan(kill_lanes=((0, -2),))
        with pytest.raises(ValueError):
            FaultPlan(preempt_at_sweep=-1)

    def test_masks_fire_on_exact_sweep(self):
        plan = FaultPlan(nan_grads=((3, 1), (3, 4), (5, 1)),
                         kill_lanes=((4, 0),))
        nan3, kill3 = injection_masks(plan, jnp.asarray(3), 6)
        np.testing.assert_array_equal(
            np.asarray(nan3), [False, True, False, False, True, False])
        assert not np.asarray(kill3).any()
        nan4, kill4 = injection_masks(plan, jnp.asarray(4), 6)
        assert not np.asarray(nan4).any()
        np.testing.assert_array_equal(
            np.asarray(kill4), [True, False, False, False, False, False])

    def test_empty_plan_empty_masks(self):
        nan, kill = injection_masks(FaultPlan(), jnp.asarray(0), 4)
        assert not np.asarray(nan).any() and not np.asarray(kill).any()


# ---------------------------------------------------------------------------
# Preempt -> resume is ARRAY-EQUAL, per (sweep_mode, schedule, lane_chunk)
# ---------------------------------------------------------------------------
PARITY_CELLS = [
    ("batched", dict(sweep_mode="batched")),
    ("per_lane", dict(sweep_mode="per_lane")),
    ("megakernel", dict(sweep_mode="megakernel")),
    ("chunk-repack-compact", dict(sweep_mode="batched", lane_chunk=4,
                                  repack_every=3, compact_every=2)),
    ("auto-chunk", dict(sweep_mode="batched", lane_chunk=4,
                        schedule="auto", schedule_every=3)),
]


class TestPreemptResumeParity:
    @pytest.mark.parametrize("name,extra",
                             PARITY_CELLS, ids=[c[0] for c in PARITY_CELLS])
    def test_resume_equals_uninterrupted(self, tmp_path, name, extra):
        x0 = _x0(10, 3, seed=1)
        base = BFGSOptions(iter_bfgs=25, theta=1e-5, **extra)
        # the reference is the UNINTERRUPTED checkpointed solve: identical
        # config and execution mode, minus the crash. (XLA compiles eager
        # and jitted programs separately, so un-jitted solves can differ
        # from any jitted path in low-order float bits — see
        # test_hosted_driver_matches_jitted_solve for the anchor.)
        ref = batched_bfgs(rosenbrock, x0, dataclasses.replace(
            base, checkpoint_every=4,
            checkpoint_dir=str(tmp_path / (name + "_ref"))))

        ck = str(tmp_path / name)
        opts = dataclasses.replace(
            base, checkpoint_every=4, checkpoint_dir=ck,
            fault_plan=FaultPlan(preempt_at_sweep=11))
        with pytest.raises(Preempted) as ei:
            batched_bfgs(rosenbrock, x0, opts)
        assert ei.value.sweep == 11
        assert ei.value.checkpoint_dir == ck
        # adversarial boundary: sweeps 9..11 died un-snapshotted
        from repro.checkpoint import manager
        assert manager.latest_step(ck) == 8

        res = batched_bfgs(
            rosenbrock, x0,
            dataclasses.replace(base, checkpoint_every=4,
                                checkpoint_dir=ck),
            resume_from=ck)
        _assert_result_equal(ref, res)

    def test_hosted_driver_matches_jitted_solve(self, tmp_path):
        """The host-segmented driver is bit-identical to the once-jitted
        in-device solve (its segments jit the same cond/body): durability
        does not change the numerics a jit user sees."""
        x0 = _x0(10, 3, seed=1)
        base = BFGSOptions(iter_bfgs=25, theta=1e-5, sweep_mode="batched")
        jitted = jax.jit(lambda x: batched_bfgs(rosenbrock, x, base))(x0)
        hosted = batched_bfgs(rosenbrock, x0, dataclasses.replace(
            base, checkpoint_every=5, checkpoint_dir=str(tmp_path / "h")))
        _assert_result_equal(jitted, hosted)

    def test_resume_lbfgs(self, tmp_path):
        """Same contract through the L-BFGS strategy (circular-buffer
        direction state snapshots through the identical carry path)."""
        x0 = _x0(8, 4, seed=2)
        base = LBFGSOptions(iter_max=25, theta=1e-5, memory=4,
                            sweep_mode="batched", lane_chunk=4)
        ref = batched_lbfgs(rosenbrock, x0, dataclasses.replace(
            base, checkpoint_every=3,
            checkpoint_dir=str(tmp_path / "lbfgs_ref")))
        ck = str(tmp_path / "lbfgs")
        with pytest.raises(Preempted):
            batched_lbfgs(rosenbrock, x0, dataclasses.replace(
                base, checkpoint_every=3, checkpoint_dir=ck,
                fault_plan=FaultPlan(preempt_at_sweep=8)))
        res = batched_lbfgs(
            rosenbrock, x0,
            dataclasses.replace(base, checkpoint_every=3,
                                checkpoint_dir=ck),
            resume_from=ck)
        _assert_result_equal(ref, res)

    def test_preempt_without_checkpointing_loses_everything(self):
        x0 = _x0(6, 2)
        with pytest.raises(Preempted) as ei:
            batched_bfgs(rosenbrock, x0, BFGSOptions(
                iter_bfgs=20, sweep_mode="batched",
                fault_plan=FaultPlan(preempt_at_sweep=5)))
        assert ei.value.checkpoint_dir is None

    def test_checkpointing_requires_dir(self):
        with pytest.raises(ValueError):
            batched_bfgs(rosenbrock, _x0(4, 2), BFGSOptions(
                iter_bfgs=5, checkpoint_every=2))

    def test_hosted_driver_rejects_tracers(self):
        opts = BFGSOptions(iter_bfgs=5, sweep_mode="batched",
                           fault_plan=FaultPlan(preempt_at_sweep=2))
        with pytest.raises(ValueError, match="jit"):
            jax.jit(lambda x: batched_bfgs(rosenbrock, x, opts))(_x0(4, 2))

    def test_keep_n_gc_during_solve(self, tmp_path):
        from repro.checkpoint import manager
        ck = str(tmp_path / "gc")
        batched_bfgs(rosenbrock, _x0(8, 3), BFGSOptions(
            iter_bfgs=30, theta=1e-30, sweep_mode="batched",
            checkpoint_every=2, checkpoint_dir=ck, checkpoint_keep=2))
        assert len(manager.committed_steps(ck)) <= 2


# ---------------------------------------------------------------------------
# Quarantine + retry: failed lanes re-enter the active set
# ---------------------------------------------------------------------------
class TestQuarantineRetry:
    def _ackley_x0(self, n=8, d=3):
        # lane 0 at the exact origin: ackley's gradient there is 0/0 = NaN
        # (paper §V-B3's blow-up case) while f(0) = 0 is finite, so the
        # lane starts active and fails organically on its first sweep
        x0 = np.array(_x0(n, d, seed=3, lo=-20.0, hi=20.0))
        x0[0] = 0.0
        return jnp.asarray(x0)

    def _min_converged(self, res):
        f = np.asarray(res.fval)
        conv = np.asarray(res.status) == CONVERGED
        assert conv.any()
        return f[conv].min()

    def test_organic_nan_lane_recovers(self):
        x0 = self._ackley_x0()
        base = BFGSOptions(iter_bfgs=60, theta=1e-4, sweep_mode="batched")
        res0 = batched_bfgs(ackley, x0, base)
        assert int(res0.n_failed) >= 1
        assert np.asarray(res0.status)[0] == DIVERGED
        assert int(np.asarray(res0.n_restarts).sum()) == 0

        retry = batched_bfgs(
            ackley, x0,
            dataclasses.replace(base, retry_budget=2, retry_sigma=0.05),
            retry_key=jax.random.key(9))
        assert int(np.asarray(retry.n_restarts)[0]) >= 1
        assert int(retry.n_failed) < int(res0.n_failed)
        # a healed solve ends no worse than abandoning the lane
        assert self._min_converged(retry) <= self._min_converged(res0) + 1e-6

    def test_injected_nan_heals_and_budget_caps(self):
        x0 = _x0(8, 3, seed=4)
        plan = FaultPlan(nan_grads=((2, 1), (2, 5)))
        base = BFGSOptions(iter_bfgs=80, theta=1e-5, sweep_mode="batched",
                           fault_plan=plan)
        broken = batched_bfgs(rosenbrock, x0, base)
        assert int(broken.n_failed) == 2

        healed = batched_bfgs(
            rosenbrock, x0, dataclasses.replace(base, retry_budget=1),
            retry_key=jax.random.key(5))
        n_restarts = np.asarray(healed.n_restarts)
        assert n_restarts[1] == 1 and n_restarts[5] == 1
        # both injected lanes healed (no longer failed) and healing wins
        # lanes outright: more converge than when abandoning them
        assert int(healed.n_failed) == 0
        assert int(healed.n_converged) > int(broken.n_converged)

    def test_kill_lane_reenters_active_set(self):
        x0 = _x0(8, 3, seed=5)
        plan = FaultPlan(kill_lanes=((3, 2),))
        healed = batched_bfgs(
            rosenbrock, x0,
            BFGSOptions(iter_bfgs=40, theta=1e-5, sweep_mode="batched",
                        lane_chunk=4, repack_every=2, fault_plan=plan,
                        retry_budget=1),
            retry_key=jax.random.key(6))
        assert int(np.asarray(healed.n_restarts)[2]) == 1
        assert np.asarray(healed.status)[2] == CONVERGED

    def test_uniform_mode_requires_bounds(self):
        with pytest.raises(ValueError, match="retry_bounds"):
            batched_bfgs(rosenbrock, _x0(4, 2), BFGSOptions(
                iter_bfgs=5, sweep_mode="batched", retry_budget=1,
                retry_mode="uniform"))

    def test_uniform_mode_reseeds_inside_bounds(self):
        x0 = self._ackley_x0()
        res = batched_bfgs(
            ackley, x0,
            BFGSOptions(iter_bfgs=60, theta=1e-4, sweep_mode="batched",
                        retry_budget=1, retry_mode="uniform",
                        retry_bounds=(-20.0, 20.0)),
            retry_key=jax.random.key(7))
        assert int(np.asarray(res.n_restarts)[0]) == 1

    def test_retry_deterministic_given_key(self):
        x0 = self._ackley_x0()
        opts = BFGSOptions(iter_bfgs=40, theta=1e-4, sweep_mode="batched",
                           retry_budget=2)
        a = batched_bfgs(ackley, x0, opts, retry_key=jax.random.key(11))
        b = batched_bfgs(ackley, x0, opts, retry_key=jax.random.key(11))
        _assert_result_equal(a, b)

    def test_retry_rejected_off_batched_paths(self):
        with pytest.raises(ValueError, match="retry_budget"):
            batched_bfgs(rosenbrock, _x0(4, 2), BFGSOptions(
                iter_bfgs=5, sweep_mode="per_lane", retry_budget=1))

    def test_resume_parity_with_retry_and_injection(self, tmp_path):
        """The hard composition: injected faults + quarantine retries +
        preemption. The retry PRNG stream lives in the carry, so the
        resumed solve replays the same re-seeds."""
        x0 = _x0(10, 3, seed=6)
        plan = FaultPlan(nan_grads=((2, 1), (6, 4)), kill_lanes=((5, 7),))
        base = BFGSOptions(iter_bfgs=30, theta=1e-5, sweep_mode="batched",
                           lane_chunk=5, repack_every=2, fault_plan=plan,
                           retry_budget=2)
        rk = jax.random.key(12)
        ref = batched_bfgs(rosenbrock, x0, dataclasses.replace(
            base, checkpoint_every=3,
            checkpoint_dir=str(tmp_path / "retry_ref")), retry_key=rk)
        assert int(np.asarray(ref.n_restarts).sum()) >= 3

        ck = str(tmp_path / "retry")
        with pytest.raises(Preempted):
            batched_bfgs(rosenbrock, x0, dataclasses.replace(
                base, checkpoint_every=3, checkpoint_dir=ck,
                fault_plan=dataclasses.replace(plan, preempt_at_sweep=8)),
                retry_key=rk)
        res = batched_bfgs(
            rosenbrock, x0,
            dataclasses.replace(base, checkpoint_every=3,
                                checkpoint_dir=ck),
            retry_key=rk, resume_from=ck)
        _assert_result_equal(ref, res)


# ---------------------------------------------------------------------------
# zeus(): driver-level resume, retry surfacing, exhaustion warning
# ---------------------------------------------------------------------------
class TestZeusFaults:
    _base = dict(use_pso=False, pso=PSOOptions(n_particles=12, iter_pso=0),
                 bfgs=BFGSOptions(iter_bfgs=30, theta=1e-4),
                 sweep_mode="batched")

    def test_zeus_resume_matches_uninterrupted(self, tmp_path):
        key = jax.random.key(2)
        ref = zeus(rosenbrock, key, 3, -5.0, 10.0,
                   ZeusOptions(checkpoint_every=4,
                               checkpoint_dir=str(tmp_path / "zref"),
                               **self._base))
        ck = str(tmp_path / "zck")
        with pytest.raises(Preempted):
            zeus(rosenbrock, key, 3, -5.0, 10.0, ZeusOptions(
                checkpoint_every=4, checkpoint_dir=ck,
                fault_plan=FaultPlan(preempt_at_sweep=10), **self._base))
        res = zeus(rosenbrock, key, 3, -5.0, 10.0,
                   ZeusOptions(checkpoint_every=4, checkpoint_dir=ck,
                               **self._base),
                   resume=ck)
        _assert_result_equal(ref.raw, res.raw)
        np.testing.assert_array_equal(np.asarray(ref.best_x),
                                      np.asarray(res.best_x))
        np.testing.assert_array_equal(np.asarray(ref.pso_best_f),
                                      np.asarray(res.pso_best_f))

    def test_zeus_surfaces_retry_counters(self):
        res = zeus(ackley, jax.random.key(3), 3, -20.0, 20.0,
                   ZeusOptions(retry_budget=1, **self._base))
        assert res.n_failed is not None and res.n_restarts is not None
        assert int(res.n_failed) == 0 or int(res.n_failed) < 12

    def test_warns_when_every_lane_failed(self):
        def poison(x):
            return jnp.sum(x) * jnp.nan  # every lane fails at init

        with pytest.warns(RuntimeWarning, match="lanes ended failed"):
            zeus(poison, jax.random.key(4), 2, -1.0, 1.0,
                 ZeusOptions(**self._base))

    def test_no_warning_on_healthy_solve(self):
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error", RuntimeWarning)
            zeus(rosenbrock, jax.random.key(5), 2, -5.0, 10.0,
                 ZeusOptions(**self._base))


# ---------------------------------------------------------------------------
# Property: resume exactness over (preempt sweep x freeze pattern x chunk
# x schedule) — counters never double-count replayed sweeps
# ---------------------------------------------------------------------------
_REF_CACHE = {}


def _frozen_mix(frozen):
    """Lanes flagged frozen start at rosenbrock's minimizer (converge on
    sweep 1) — the tail regimes exercise compaction/repack paths around
    the checkpoint boundaries."""
    x0 = np.array(_x0(len(frozen), 3, seed=8, lo=-2.0, hi=2.0))
    x0[np.asarray(frozen)] = 1.0
    return jnp.asarray(x0)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=int(os.environ.get("REPRO_HYPOTHESIS_MAX_EXAMPLES",
                                          "12")),
          deadline=None)
@given(
    preempt=st.integers(min_value=3, max_value=14),
    frozen=st.lists(st.booleans(), min_size=8, max_size=8),
    chunk=st.sampled_from([None, 4]),
    schedule=st.sampled_from(["static", "auto"]),
)
def test_property_resume_exact(preempt, frozen, chunk, schedule):
    x0 = _frozen_mix(frozen)
    base = BFGSOptions(
        iter_bfgs=18, theta=1e-6, sweep_mode="batched", lane_chunk=chunk,
        # auto owns the cadence plan — explicit repack_every is
        # static-schedule only
        repack_every=2 if (chunk and schedule == "static") else 0,
        schedule=schedule, schedule_every=3)
    key = (tuple(frozen), chunk, schedule)
    if key not in _REF_CACHE:
        ckref = tempfile.mkdtemp(prefix="faults_prop_ref_")
        try:
            _REF_CACHE[key] = batched_bfgs(
                rosenbrock, x0,
                dataclasses.replace(base, checkpoint_every=2,
                                    checkpoint_dir=ckref))
        finally:
            shutil.rmtree(ckref, ignore_errors=True)
    ref = _REF_CACHE[key]

    ck = tempfile.mkdtemp(prefix="faults_prop_")
    try:
        try:
            batched_bfgs(rosenbrock, x0, dataclasses.replace(
                base, checkpoint_every=2, checkpoint_dir=ck,
                fault_plan=FaultPlan(preempt_at_sweep=preempt)))
        except Preempted:
            pass  # solves that finish before `preempt` simply complete
        res = batched_bfgs(
            rosenbrock, x0,
            dataclasses.replace(base, checkpoint_every=2,
                                checkpoint_dir=ck),
            resume_from=ck)
        # trajectories, statuses, eval_rows, map_trips, schedule_trace:
        # all array-equal, so replayed sweeps were not double-counted
        _assert_result_equal(ref, res)
    finally:
        shutil.rmtree(ck, ignore_errors=True)


# ---------------------------------------------------------------------------
# distributed_zeus: per-shard snapshots, same-shard exactness, elastic
# restore onto a different shard count
# ---------------------------------------------------------------------------
def _run_subprocess(code: str, devices: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


_DIST_PREEMPT = """
    import dataclasses, shutil
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import BFGSOptions, PSOOptions, ZeusOptions
    from repro.core.distributed import distributed_zeus
    from repro.core.objectives import rosenbrock
    from repro.launch.faults import FaultPlan, Preempted
    from repro.sharding import make_mesh

    CK = {ck!r}
    mesh = make_mesh((2,), ("data",))
    base = dict(use_pso=False, pso=PSOOptions(n_particles=16, iter_pso=0),
                bfgs=BFGSOptions(iter_bfgs=40, theta=1e-4, required_c=16),
                sweep_mode="batched", lane_chunk=4, repack_every=2)
    key = jax.random.key(3)

    # reference = the UNINTERRUPTED segmented solve (same execution mode
    # as the resumed run; eager/fast-path XLA programs can differ in
    # low-order float bits from the segmented jit)
    ref = distributed_zeus(rosenbrock, 2, -5.0, 10.0, ZeusOptions(
        checkpoint_every=4, checkpoint_dir=CK + "_ref", **base), mesh)(key)
    shutil.rmtree(CK + "_ref", ignore_errors=True)
    try:
        distributed_zeus(rosenbrock, 2, -5.0, 10.0, ZeusOptions(
            checkpoint_every=4, checkpoint_dir=CK,
            fault_plan=FaultPlan(preempt_at_sweep=10), **base), mesh)(key)
        raise SystemExit("no preemption")
    except Preempted:
        pass
    np.savez(CK + "_ref.npz", status=np.asarray(ref.raw.status),
             x=np.asarray(ref.raw.x), fval=np.asarray(ref.raw.fval),
             best_f=np.asarray(ref.best_f), best_x=np.asarray(ref.best_x),
             eval_rows=np.asarray(ref.raw.eval_rows),
             map_trips=np.asarray(ref.raw.map_trips),
             iterations=np.asarray(ref.raw.iterations))
    print("SAVED")
"""

_DIST_RESUME = """
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import BFGSOptions, PSOOptions, ZeusOptions
    from repro.core.distributed import distributed_zeus
    from repro.core.objectives import rosenbrock
    from repro.sharding import make_mesh

    CK = {ck!r}
    DEV = {devices}
    EXACT = {exact}
    mesh = make_mesh((DEV,), ("data",))
    base = dict(use_pso=False, pso=PSOOptions(n_particles=16, iter_pso=0),
                bfgs=BFGSOptions(iter_bfgs=40, theta=1e-4, required_c=16),
                sweep_mode="batched", lane_chunk=4, repack_every=2)
    key = jax.random.key(3)
    run = distributed_zeus(rosenbrock, 2, -5.0, 10.0, ZeusOptions(
        checkpoint_every=4, checkpoint_dir=CK + "_cont", **base), mesh)
    res = run(key, resume_from=CK)
    ref = np.load(CK + "_ref.npz")
    for fld in ("status", "x", "fval", "best_f", "best_x"):
        np.testing.assert_array_equal(ref[fld],
                                      np.asarray(getattr(res.raw, fld))
                                      if fld in ("status", "x", "fval")
                                      else np.asarray(getattr(res, fld)),
                                      err_msg=fld)
    assert int(res.raw.iterations) == int(ref["iterations"])
    if EXACT:
        # same shard count: the whole-mesh work counters replay exactly too
        assert int(res.raw.eval_rows) == int(ref["eval_rows"])
        assert int(res.raw.map_trips) == int(ref["map_trips"])
    print("RESUMED", int(res.raw.iterations))
"""


@pytest.mark.parametrize("devices,exact", [(2, True), (4, False)],
                         ids=["same-shard", "elastic-reshard"])
def test_distributed_preempt_resume(tmp_path, devices, exact):
    """Preempt a 2-shard distributed solve, then resume it — once onto the
    same mesh (everything exact, counters included) and once onto a
    4-device mesh (elastic: lane trajectories and minima are shard-count
    invariant; the per-shard repack bucketing counters are not)."""
    ck = str(tmp_path / "dck")
    out = _run_subprocess(_DIST_PREEMPT.format(ck=ck), devices=2)
    assert "SAVED" in out
    out = _run_subprocess(
        _DIST_RESUME.format(ck=ck, devices=devices, exact=exact),
        devices=devices)
    assert "RESUMED" in out
