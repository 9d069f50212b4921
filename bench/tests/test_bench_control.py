"""The control of `correct`: the nearest precision below the one the
configuration states fails the cell's limits, where the program's own
solves pass them. Below float32 it is the reference in bfloat16 in the
program's place; below float64, the program's own float32 path. At a size
a CPU test run holds; on the chip at the cells' own sizes it is
`bench/calibrate.py`."""
import _tiny
import pytest

import calibrate
import reference

SEEDS = [2**31 + 5, 17]
# what the control fails by here, by 3x or more: bfloat16's rounding moves
# the swarm and leaves no converged lane stationary; float32's moves the
# values and the best (the swarm's best and the gradient at the fit move
# too, but by less than 3x their limits here: 1e-7 and ~0.1 on the chip)
FAILED_BY = {"float32": {"conv_grad", "pso_gap"},
             "float64": {"fval_gap", "best_gap"}}


@pytest.fixture(autouse=True)
def _jnp_kernels(monkeypatch):
    monkeypatch.setenv("REPRO_DISABLE_PALLAS", "1")


@pytest.mark.parametrize("name", sorted(_tiny.SIZES))
def test_control_fails_where_the_program_passes(name):
    cell = _tiny.tiny_cell(name)
    limits = cell["cfg"]["limits"]
    for line in calibrate.calibrate(cell, SEEDS, 0.3, len(SEEDS),
                                    require_kernel=False):
        assert line["program_failed"] == 0 and line["control_failed"] == 0
        ok, checks = reference.judge([line["program"]], limits)
        assert ok, checks
        ok, checks = reference.judge([line["control"]], limits)
        assert not ok, checks
        # the control fails by several numbers, each by a wide margin
        over = {k for k in reference.NAMES
                if checks[k]["value"] > 3 * checks[k]["limit"]}
        assert FAILED_BY[cell["cfg"]["dtype"]] <= over, checks
