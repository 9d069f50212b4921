"""The exact checks of the sweep loop (reference.loop_gaps) on answers made
by hand: each lane's status, each active lane's sweeps by its eval counter,
and the stop rule, no earlier and no later."""
import _paths  # noqa: F401
import numpy as np
import pytest

import reference as ref

D, C, S = ref.DIVERGED, ref.CONVERGED, ref.STOPPED
VG, K = 2, 20  # value+grad cost, ladder rungs: n_evals = VG + s*(K + VG)


def _cfg(iter_bfgs, required_c=None):
    b = {"iter_bfgs": iter_bfgs, "theta": 1e-4}
    if required_c is not None:
        b["required_c"] = required_c
    return {"zeus": {"bfgs": b}}


def _ans(status, sweeps, k, fval=None):
    n = len(status)
    return {"status": np.array(status, np.int32),
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
    assert ref.loop_gaps(ans, _cfg(kmax, need), VG) == want


def test_a_counter_that_does_not_decode_fails_every_lane():
    ans = _ans([C, D], [1, 3], 3)
    ans["n_evals"] = ans["n_evals"] + 1
    assert ref.loop_gaps(ans, _cfg(3), VG) == (0, 2, 1)


def test_worst_reads_nan_as_infinite():
    assert ref._worst([0.1, np.nan]) == np.inf
    assert ref._worst([]) == np.inf
    assert ref._worst([0.1, 0.3]) == 0.3
