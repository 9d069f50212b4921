"""The reduction from trace events to busy time, idle share, per-op time and
breakdown, on hand-made events and on a small trace recorded on a v5e."""
import base64
import json
from pathlib import Path

import _paths  # noqa: F401
import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
# one solve of a small batched fit (a 4-parameter dijet NLL, 512 lanes)
# traced on one v5e (TPU v5 lite), and the kernel names of its compiled
# program (kernel_names of its text)
FIXTURE = DATA / "dijet_one_fit.xplane.pb"
KERNELS = DATA / "dijet_one_fit.kernels.json"


def _events():
    # two chips; times in ns. Host: solve spans [0, 100) and [120, 200),
    # each with dispatch / wait / readback inside.
    host = [("bench.solve", 0, 100), ("bench.dispatch", 0, 10),
            ("bench.wait", 10, 90), ("bench.readback", 90, 100),
            ("bench.solve", 120, 200), ("bench.dispatch", 120, 125),
            ("bench.wait", 125, 195), ("bench.readback", 195, 200)]
    chip0 = [("fusion.1", 10, 40), ("kernel_a", 40, 80),
             ("fusion.1", 130, 190), ("kernel_a", 195, 230)]  # clipped at 200
    chip1 = [("fusion.1", 0, 200)]
    return [chip0, chip1], host


def test_union_and_gaps():
    assert tr.union([(10, 50), (40, 80), (90, 95), (-5, 3)], 0, 92) == [
        [0, 3], [10, 80], [90, 92]]
    assert tr.gaps([[0, 3], [10, 80]], 0, 100) == [(3, 10), (80, 100)]


def test_reduce_by_hand():
    s = tr.reduce(*_events())
    # window 0..200 ns; chip 0 busy 70 + 60 + 5 = 135, chip 1 busy 200
    assert s.window_s == pytest.approx(200e-9)
    assert s.busy_s == pytest.approx((135 + 200) / 2 * 1e-9)
    assert s.idle_share == pytest.approx(1 - 167.5 / 200)
    assert sum(v for _, v in s.breakdown["idle_gaps"]) == pytest.approx(
        s.window_s - s.busy_s)
    assert s.n_solves == 2
    # fusion.1 on chip 0: 30 + 60, on chip 1: 200 -> mean 145 ns
    assert s.op_s["fusion.1"] == pytest.approx(145e-9)
    assert s.op_s["kernel_a"] == pytest.approx((40 + 5) / 2 * 1e-9)
    assert s.breakdown["device_ops"][0] == ["fusion.1", pytest.approx(145e-9)]
    # chip 0 idle: 0..10 (dispatch); 80..130, cut into wait 80..90, readback
    # 90..100, between solves 100..120, dispatch 120..125, wait 125..130;
    # 190..195 (wait). Chip 1 is never idle; the mean is over both chips.
    gaps = dict(s.breakdown["idle_gaps"])
    assert gaps["bench.dispatch"] == pytest.approx(15 / 2 * 1e-9)
    assert gaps["bench.wait"] == pytest.approx(20 / 2 * 1e-9)
    assert gaps["bench.readback"] == pytest.approx(10 / 2 * 1e-9)
    assert gaps[tr.OUTSIDE] == pytest.approx(20 / 2 * 1e-9)


def test_nested_ops_count_their_self_time():
    # a while loop 0..100 holds a body 10..60 holding a kernel 20..50
    ops = [("while.1", 0, 100), ("body.2", 10, 60), ("closed_call.3", 20, 50),
           ("fusion.4", 70, 90)]
    st = tr.self_times(ops, 0, 80)
    # clipped at 80: the loop's 80 less its body's 50 and fusion's 10
    assert st == {"while.1": 20.0, "body.2": 20.0, "closed_call.3": 30.0,
                  "fusion.4": 10.0}
    s = tr.reduce([ops], [("bench.solve", 0, 100)], {"closed_call.3": "_k"})
    assert s.busy_s == pytest.approx(100e-9)
    assert s.op_s["_k (closed_call.3)"] == pytest.approx(30e-9)
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s)


def test_kernel_names_from_program_text():
    body = base64.b64encode(b"\x01kernels\x00_my_update_kernel\x00x_body")
    text = (
        '%fusion.1 = f32[8] fusion(%p), kind=kLoop\n'
        '  %closed_call.65 = (f32[8,128,128]) custom-call(%pad.99), '
        'custom_call_target="tpu_custom_call", backend_config={"flag_configs":'
        '[],"custom_call_config":{"body":"' + body.decode() + '"}}\n')
    assert tr.kernel_names(text) == {"closed_call.65": "_my_update_kernel"}
    assert tr.short_name("%fusion.87 = (f32[512,4]) fusion(f32[4] %a)") == "fusion.87"


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError):
        tr.reduce([], [("bench.solve", 0, 1)])


def test_recorded_chip_trace():
    s = tr.reduce(*tr.load(FIXTURE), json.loads(KERNELS.read_text()))
    assert s.n_solves == 1
    assert 0 < s.busy_s <= s.window_s < 0.1
    assert 0 <= s.idle_share < 1
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s, rel=1e-6)
    # the fit's update kernel is its largest op, found by its kernel name
    top, top_s = s.breakdown["device_ops"][0]
    assert top.startswith("_guarded_update_direction_kernel (")
    assert top_s > 0.5 * s.busy_s
    assert len(s.breakdown["device_ops"]) == tr.TOP
    assert {n for n, _ in s.breakdown["idle_gaps"]} <= {
        "bench.solve", "bench.dispatch", "bench.wait", "bench.readback",
        tr.OUTSIDE}


def test_only_the_solve_chips_count():
    devices, host = tr.load(FIXTURE)
    assert len(devices) == 1
    assert tr.load(FIXTURE, chips=[0])[0] == devices
    # a chip the solve does not run on is left out, and with no chip left
    # there is nothing to reduce
    assert tr.load(FIXTURE, chips=[1])[0] == []
    with pytest.raises(ValueError):
        tr.reduce(*tr.load(FIXTURE, chips=[1]))
    # an idle chip beside the solve's would halve its busy share
    both = tr.reduce(devices + [[]], host)
    one = tr.reduce(devices, host)
    assert both.busy_s == pytest.approx(one.busy_s / 2)
