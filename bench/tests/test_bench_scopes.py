"""Device time per named scope: op names mapped to scopes through the
compiled program's text, on hand-written HLO lines, a hand-made trace
Summary, and the compiled text of a tiny fig1 solve."""
import base64
import re

import _paths  # noqa: F401
import pytest

import _tiny
import harness
import scopes
import spec
import trace_reduce as tr

READERS = ("phase1_ms", "ladder_ms", "update_stage_ms", "fused_sweep_ms")
BODY = base64.b64encode(b"\x01_guarded_update_direction_kernel\x00").decode()
TEXT = "\n".join([
    '%fused_computation (param_0: f32[8]) -> f32[8] {',
    '  %mul.2 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name='
    '"jit(solve)/zeus.phase2/while/body/zeus.phase2.ladder/mul"}',
    '}',
    'ENTRY %main (p: f32[8]) -> f32[8] {',
    '  %p = f32[8]{0} parameter(0)',
    '  %rng.1 = f32[8]{0} add(%p, %p), metadata={op_name='
    '"jit(solve)/zeus.phase1/init_swarm/add" stack_frame_id=3}',
    '  %ladder_fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, '
    'calls=%fused_computation, metadata={op_name="jit(solve)/zeus.phase2/'
    'while/body/zeus.phase2.ladder/mul"}',
    '  %pad.99 = f32[8,128]{1,0} pad(%p), metadata={op_name="jit(solve)/'
    'zeus.phase2/while/body/closed_call/vmap(zeus.phase2.update)/pad"}',
    '  %zeus.phase2.update.7 = f32[8,128]{1,0} custom-call(%pad.99), '
    'custom_call_target="tpu_custom_call", backend_config={"custom_call_'
    'config":{"body":"' + BODY + '"}}, metadata={op_name="jit(solve)/'
    'zeus.phase2/while/body/zeus.phase2.update/pallas_call"}',
    '  %copy.202 = f32[8]{0} copy(%p)',
    '  ROOT %argmin.4 = f32[] reduce(%p), metadata={op_name='
    '"jit(solve)/zeus.finale/argmin"}',
    '}',
])
KERNEL = "_guarded_update_direction_kernel (zeus.phase2.update.7)"
OP_S = {"rng.1": 0.02, "ladder_fusion.3": 0.5, "pad.99": 0.3, KERNEL: 2.0,
        "copy.202": 0.1, "argmin.4": 0.08}


def _ctx(trace=None, text=TEXT, monkeypatch=None):
    if monkeypatch is not None:
        monkeypatch.setattr(scopes, "program_text", lambda cfg, problem: text)
    return harness.Context(cfg={}, trace=trace, problem=None, answers=[],
                           device_kind="TPU v5 lite")


def _summary(op_s=OP_S, n_solves=2):
    busy = sum(op_s.values())
    return tr.Summary(busy_s=busy, window_s=busy * 1.01,
                      idle_share=1 - 1 / 1.01, op_s=dict(op_s),
                      n_solves=n_solves, breakdown={})


def test_scope_of_hand_written_lines():
    s = scopes.scope_of(TEXT)
    # the innermost zeus.* scope, inside a transform's name too
    assert s["mul.2"] == "zeus.phase2.ladder"
    assert s["ladder_fusion.3"] == "zeus.phase2.ladder"
    assert s["pad.99"] == "zeus.phase2.update"
    assert s["rng.1"] == "zeus.phase1" and s["argmin.4"] == "zeus.finale"
    # a kernel's custom call carries its call site's scope
    assert s["zeus.phase2.update.7"] == "zeus.phase2.update"
    # no metadata, or no zeus scope in it: None
    assert s["copy.202"] is None and s["p"] is None


def test_seconds_per_scope_sums_to_busy_time(monkeypatch):
    trace = _summary()
    split = scopes.seconds_per_scope(_ctx(trace, monkeypatch=monkeypatch))
    assert sum(split.values()) == pytest.approx(
        trace.busy_s / trace.n_solves, rel=1e-12)
    assert split["zeus.phase2.update"] == pytest.approx((0.3 + 2.0) / 2)
    assert split[None] == pytest.approx(0.1 / 2)
    ctx = _ctx(trace)
    # the update stage less its kernel: the pad alone
    assert spec.metric_reader("update_stage_ms").read(
        ctx) == pytest.approx(150.0)
    assert spec.metric_reader("ladder_ms").read(ctx) == pytest.approx(250.0)
    assert spec.metric_reader("phase1_ms").read(ctx) == pytest.approx(10.0)


def test_update_stage_reads_zero_once_its_staging_is_gone(monkeypatch):
    op_s = {k: v for k, v in OP_S.items() if k != "pad.99"}
    ctx = _ctx(_summary(op_s), monkeypatch=monkeypatch)
    assert spec.metric_reader("update_stage_ms").read(ctx) == 0.0


@pytest.mark.parametrize("op_s", [
    dict(OP_S, **{"fusion.1": 0.1}),  # an op the text does not hold
    {("_value_kernel (zeus.phase2.update.7)" if k == KERNEL else k): v
     for k, v in OP_S.items()},  # a kernel labelled otherwise
    {("zeus.phase2.update.7" if k == KERNEL else k): v
     for k, v in OP_S.items()},  # a kernel not labelled at all
])
def test_a_mismatched_text_gives_none(monkeypatch, op_s):
    ctx = _ctx(_summary(op_s), monkeypatch=monkeypatch)
    assert scopes.seconds_per_scope(ctx) is None
    for name in READERS:
        assert spec.metric_reader(name).read(ctx) is None


def test_a_program_without_scopes_gives_none(monkeypatch):
    bare = re.sub(r'op_name="[^"]*"', 'op_name="jit(solve)/add"', TEXT)
    ctx = _ctx(_summary(), text=bare, monkeypatch=monkeypatch)
    assert set(scopes.seconds_per_scope(ctx)) == {None}
    for name in READERS:
        assert spec.metric_reader(name).read(ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_without_a_trace(name):
    assert spec.metric_reader(name).read(_ctx()) is None


def test_tiny_fig1_program_holds_every_batched_scope():
    cfg = _tiny.tiny_cell("rastrigin-d10.fig1")["cfg"]
    text = scopes.program_text(cfg, spec.problem_module(cfg))
    assert scopes.program_text(cfg, None) is text  # one compile per process
    assert {"zeus.phase1", "zeus.phase2", "zeus.phase2.ladder",
            "zeus.phase2.gradient", "zeus.phase2.update",
            "zeus.phase2.accept", "zeus.finale"} <= set(
                scopes.scope_of(text).values())
