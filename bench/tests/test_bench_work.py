"""Required-work counts of bench/work.py against hand counts, and the peak
table's refusal of a device it does not know."""
import _paths  # noqa: F401
import numpy as np
import pytest

import spec
import work


def _problem(config_name):
    bench = spec.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}[config_name]
    import json
    with open(spec.ROOT / entry["file"]) as fh:
        cfg = json.load(fh)
    return cfg, spec.problem_module(cfg)


def test_rastrigin_d10_lane_sweep_by_hand():
    cfg, prob = _problem("rastrigin-d10")
    # update: Hy, y.Hy, three rank-one terms, p = -H'g -> 10*D^2 + 6*D flops;
    # H read + written and s, y, g, p -> (2*D^2 + 4*D) floats
    assert work.update_work(10, 4) == (1060, 960)
    # value: 6 flops/coord, x read + f written; value+grad: 11 flops/coord,
    # x read, f and g written
    assert prob.row_work(cfg) == {"value": (60, 44), "value_grad": (110, 84)}
    # trial x + a*p: 20 flops, x and p read (the value row's own x read is
    # the trial) -> 20 + 60 + 110 + 1060 flops, 80 - 40 + 44 + 84 + 960 bytes
    assert work.lane_sweep_work(10, prob.row_work(cfg), 4) == (1250, 1128)


def test_lane_sweeps_decode_the_counter():
    # n_evals = c + s*(K + c): c = 2 (fused value+grad), K = 20 rungs
    assert work.lane_sweeps(np.array([2, 24, 68]), 2, 20).tolist() == [0, 1, 3]
    assert work.lane_sweeps(np.array([2, 69]), 2, 20) is None
    assert work.lane_sweeps(np.array([1]), 2, 20) is None


def test_solve_work_sums_lanes():
    cfg, prob = _problem("rastrigin-d10")
    flops, nbytes = work.solve_work(cfg, prob, np.array([2, 24, 68]))
    # 4 lane-sweeps and 3 initial value+grad rows
    assert (flops, nbytes) == (4 * 1250 + 3 * 110, 4 * 1128 + 3 * 84)


def test_solve_work_at_the_configurations_dtype_and_path():
    cfg, prob = _problem("rastrigin-d10")
    f64 = dict(cfg, dtype="float64")
    flops, nbytes = work.solve_work(f64, prob, np.array([2, 24, 68]))
    assert (flops, nbytes) == (4 * 1250 + 3 * 110, 2 * (4 * 1128 + 3 * 84))
    # a per-lane sweep's counter bounds its sweeps: no required work counted
    per_lane = dict(cfg, zeus=dict(cfg["zeus"], sweep_mode="per_lane"))
    assert work.solve_work(per_lane, prob, np.array([2, 24, 68])) is None


def test_least_time_takes_the_larger_bound():
    peak = work.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 8.19e11 and peak["flops_per_s"] == 1.97e14
    assert work.least_time(1.97e14, 8.19e11 / 2, peak) == pytest.approx(1.0)
    assert work.least_time(0.0, 8.19e11, peak) == pytest.approx(1.0)


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


def _ctx(answers, busy_s=2.0, n_solves=2, op_s=None):
    import harness
    import trace_reduce

    cfg, prob = _problem("rastrigin-d10")
    summary = trace_reduce.Summary(busy_s=busy_s, window_s=2 * busy_s,
                                   idle_share=0.5, op_s=op_s or {},
                                   n_solves=n_solves, breakdown={})
    return harness.Context(cfg=cfg, trace=summary, problem=prob,
                           answers=answers, device_kind="TPU v5 lite")


def test_metric_readers_by_hand():
    # two solves of 3 lanes: 0, 1 and 3 active sweeps each; 21 rows per
    # active lane-sweep against 420 rows evaluated per solve
    a = {"n_evals": np.array([2, 24, 68]), "eval_rows": np.int32(420)}
    ctx = _ctx([a, a], op_s={"_guarded_update_direction_kernel (c.1)": 0.5,
                             "fusion.3": 0.1})
    read = lambda n: spec.metric_reader(n).read(ctx)
    assert read("useful_eval_share") == pytest.approx(2 * 4 * 21 / 840)
    assert read("update_kernel_ms") == pytest.approx(250.0)
    assert read("device_idle_share") == 0.5
    least = (4 * 1128 + 3 * 84) / 8.19e11  # byte-bound, per solve
    assert read("sweep_roofline") == pytest.approx(100 * least / 1.0)


def test_metric_readers_find_nothing_to_read():
    bad = {"n_evals": np.array([2, 25]), "eval_rows": np.int32(0)}
    ctx = _ctx([bad], op_s={"fusion.3": 0.1})
    for name in ("useful_eval_share", "update_kernel_ms", "sweep_roofline"):
        assert spec.metric_reader(name).read(ctx) is None
