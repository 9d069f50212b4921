"""The exact checks of the sweep loop (reference.loop_gaps) on answers made
by hand: each lane's status, each active lane's sweeps by its eval counter,
and the stop rule, no earlier and no later; on a whole-ladder (batched)
path and on the per-lane path, whose counter bounds a lane's sweeps."""
import _paths  # noqa: F401
import numpy as np
import pytest

import reference as ref

D, C, S = ref.DIVERGED, ref.CONVERGED, ref.STOPPED
VG, K = 2, 20  # value+grad cost, ladder rungs: n_evals = VG + s*(K + VG)


def _cfg(iter_bfgs, required_c=None, sweep_mode="batched"):
    b = {"iter_bfgs": iter_bfgs, "theta": 1e-4}
    if required_c is not None:
        b["required_c"] = required_c
    return {"zeus": {"bfgs": b, "sweep_mode": sweep_mode},
            "dtype": "float64"}


def _grad_at(x):
    """The by-hand lanes' float64 gradient: x itself (one component)."""
    return x


def _gaps(ans, cfg):
    return ref.loop_gaps(ans, cfg, VG, ref.failed_lanes(ans, _grad_at)[0])


def _ans(status, sweeps, k, fval=None):
    n = len(status)
    return {"status": np.array(status, np.int32), "x": np.zeros((n, 1)),
            "n_evals": VG + np.array(sweeps) * (K + VG),
            "iterations": k,
            "fval": np.ones(n) if fval is None else np.array(fval),
            "grad_norm": np.ones(n)}


CASES = [
    # (name, statuses, active sweeps, sweeps taken, iter_bfgs, required_c,
    #  fvals, (status_gap, sweep_gap, stop_gap))
    ("every lane to the last sweep", [C, D, D], [2, 3, 3], 3, 3, None, None,
     (0, 0, 0)),
    ("all converged before the last", [C, C], [1, 2], 2, 5, None, None,
     (0, 0, 0)),
    ("a lane skipped a sweep", [C, D, D], [2, 3, 2], 3, 3, None, None,
     (0, 1, 0)),
    ("a lane left out of phase 2", [D, D], [3, 0], 3, 3, None, None,
     (0, 1, 0)),
    ("more sweeps than taken", [C, D], [4, 3], 3, 3, None, None, (0, 1, 0)),
    ("stopped at required_c", [C, C, S], [1, 2, 2], 2, 5, 2, None, (0, 0, 0)),
    ("stopped lane called diverged", [C, C, D], [1, 2, 2], 2, 5, 2, None,
     (1, 0, 0)),
    ("stopped before required_c", [C, S, S], [1, 2, 2], 2, 5, 2, None,
     (0, 0, 1)),
    ("ran on past required_c", [C, C, S], [1, 1, 2], 2, 5, 2, None,
     (0, 0, 1)),
    ("ran a sweep with no lane active", [C, C], [1, 1], 2, 5, None, None,
     (0, 0, 1)),
    ("more sweeps than allowed", [D], [4], 4, 3, None, None, (0, 0, 1)),
    ("a failed lane stops early", [D, D], [1, 3], 3, 3, None,
     [np.inf, 1.0], (0, 0, 0)),
    ("a failed lane called stopped", [C, C, S], [1, 2, 1], 2, 5, 2,
     [1.0, 1.0, np.nan], (1, 0, 0)),
    ("a status code that does not exist", [C, 7], [1, 3], 3, 3, None, None,
     (1, 0, 0)),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_loop_gaps_by_hand(case):
    _, status, sweeps, k, kmax, need, fval, want = case
    ans = _ans(status, sweeps, k, fval)
    assert _gaps(ans, _cfg(kmax, need)) == want


# per lane: n_evals = VG + sum over its sweeps of (rungs tried + VG), each
# sweep trying 1 to K rungs; (statuses, rungs tried per active sweep, sweeps
# taken, iter_bfgs, required_c, (status_gap, sweep_gap, stop_gap))
PER_LANE = [
    ("every lane to the last sweep", [C, D], [[3, 1], [20, 20, 1]], 3, 3,
     None, (0, 0, 0)),
    ("stopped at required_c", [C, C, S], [[1], [5, 2], [20, 7]], 2, 9, 2,
     (0, 0, 0)),
    ("a live lane skipped a sweep", [C, S, S], [[1], [1], [2, 2]], 2, 9, 2,
     (0, 1, 1)),
    ("a sweep that tried no rung", [D], [[0]], 1, 1, None, (0, 1, 1)),
    ("a lane left out of phase 2", [D, D], [[4, 4, 4], []], 3, 3, None,
     (0, 1, 0)),
    ("ran on past required_c", [C, C, S], [[2], [1], [3, 3]], 2, 9, 2,
     (0, 0, 1)),
    ("stopped before required_c", [C, S, S], [[1], [1, 1], [1, 1]], 2, 9, 2,
     (0, 0, 1)),
]


@pytest.mark.parametrize("case", PER_LANE, ids=[c[0] for c in PER_LANE])
def test_per_lane_loop_gaps_by_hand(case):
    _, status, rungs, k, kmax, need, want = case
    ans = _ans(status, [0] * len(status), k)
    ans["n_evals"] = np.array([VG + sum(r + VG for r in tried)
                               for tried in rungs])
    assert _gaps(ans, _cfg(kmax, need, "per_lane")) == want


def test_a_counter_that_does_not_decode_fails_every_lane():
    ans = _ans([C, D], [1, 3], 3)
    ans["n_evals"] = ans["n_evals"] + 1
    assert _gaps(ans, _cfg(3)) == (0, 2, 1)


def test_worst_reads_nan_as_infinite():
    assert ref._worst([0.1, np.nan]) == np.inf
    assert ref._worst([]) == np.inf
    assert ref._worst([0.1, 0.3]) == 0.3


def test_an_overflowed_gradient_norm_counts_as_its_status_says():
    # stopped at required_c = 2 after 2 sweeps; lanes 2-5 report a finite
    # value and a norm that overflowed (inf, or NaN as XLA's float64 on a
    # TPU reads it), where the reference's gradient is near overflow too
    # (1e20, or past float64's range). Lane 2 STOPPED, active in both
    # sweeps: active. Lane 3 DIVERGED after 1 sweep: failed. Lane 4 STOPPED
    # after 1 sweep: an active lane that skipped a sweep. Lane 5 DIVERGED
    # after 3 sweeps: more sweeps than taken. Lane 6's value is infinite:
    # failed, with no gradient asked for.
    ans = _ans([C, C, S, D, S, D, D], [1, 2, 2, 1, 1, 3, 1], 2,
               fval=[1.0, 1.0, 1e30, 1e38, 1e30, 1e30, np.inf])
    ans["grad_norm"] = np.array([1.0, 1.0, np.inf, np.nan, np.nan, np.inf,
                                 np.inf])
    ans["x"][2:6, 0] = [1e20, np.inf, -1e18, 1e300]
    failed, deferred, wrong = ref.failed_lanes(ans, _grad_at)
    assert failed.tolist() == [False, False, False, True, False, True, True]
    assert deferred.tolist() == [False, False, True, True, True, True, False]
    assert not wrong.any()
    assert ref.loop_gaps(ans, _cfg(5, 2), VG, failed) == (0, 2, 0)
    ans["status"][4] = D  # now lane 4 failed after its one sweep
    assert _gaps(ans, _cfg(5, 2)) == (0, 1, 0)


@pytest.mark.parametrize("status", [S, D], ids=["stopped", "diverged"])
def test_a_non_finite_norm_where_the_gradient_is_small_is_caught(status):
    """A lane whose value is finite and whose norm reads NaN, where the
    reference's gradient is 3 (far from overflow), has a wrong norm
    whatever status the program gives it: status_gap counts it, and the
    lane is not counted among the overflowed."""
    ans = _ans([C, C, status], [1, 2, 1], 2)
    ans["grad_norm"][2] = np.nan
    ans["x"][2, 0] = 3.0
    failed, deferred, wrong = ref.failed_lanes(ans, _grad_at)
    assert wrong.tolist() == [False, False, True] and not deferred.any()
    out, why = ref.readings(ans | {"best_x": np.zeros(1), "best_f": 0.0,
                                   "pso_best_f": 0.0, "n_converged": 2},
                            _ByHand, _cfg(5, 2), None, 0.0)
    assert why is None
    assert out["status_gap"] >= 1 and out["overflow_lanes"] == 0


class _ByHand:
    """A problem whose value is 0 and whose gradient is x."""

    @staticmethod
    def value(x, data, cfg):
        return np.zeros(len(x))

    @staticmethod
    def grad(x, data, cfg):
        return x

    @staticmethod
    def vg_cost(cfg):
        return VG
