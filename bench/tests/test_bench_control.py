"""The control of `correct`: the reference put in the program's place in
bfloat16, the nearest precision below the float32 the configurations state,
fails the cell's limits, where the program's own solves pass them. At a
size a CPU test run holds; on the chip at the cells' own sizes it is
`bench/calibrate.py`."""
import _tiny
import pytest

import calibrate
import reference

SEEDS = [2**31 + 5, 17]


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
        over = [k for k, c in checks.items() if c["value"] > 3 * c["limit"]]
        assert {"conv_grad", "pso_gap"} <= set(over), checks
