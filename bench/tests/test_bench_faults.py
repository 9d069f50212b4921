"""A whole run of each cell at a small size on the CPU, with the device
check left out: sound, it comes out correct; with the solve broken
underneath, it comes out not correct, once for each fault a cell can have.

- frozen: the phase-2 sweep returns its lanes unchanged;
- half: half of the lanes left out of the converged count and the finale;
- skip_half: every sweep steps half of the lanes and leaves the rest at
  their swarm start;
- early_stop: the sweep loop stops after half of its sweeps;
- altered: the answer altered where it is produced (best_x moved off the
  best lane);
- f32_build (float64 cells): the same configuration built in float32;
- f32_replay (float64 cells): the reference's swarm drawn in float32
  against the float64 program's;
- cells whose solve carries many toys: shifted_toys (each toy's answer
  handed to its neighbour, so it is judged against another toy's key and
  counts), raised_sweeps (in each solve the toy with the fewest sweeps
  reports more sweeps than it ran: the batch's most, or more where its
  lanes' eval counters admit that many), neighbour_pso (each toy reports
  its neighbour's swarm best).

A per-lane cell has no skip_half (its step is vmapped over single lanes,
so no step sees a batch to halve) and no early_stop (the dijet fit stops
on required_c after ~15 of its 300 sweeps, so halving iter_max changes
nothing). Each cell's solve runs on one chip, so there is no exchange
between chips to leave out.
"""
import dataclasses
import importlib
import time

import _tiny
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference
import spec
import work

SEED = 2**31 + 3


def frozen(monkeypatch):
    from repro.core import engine

    step = engine.batch_lanes_step

    def unchanged(bobj, bstrategy, opts, lanes):
        _, rows, hist = step(bobj, bstrategy, opts, lanes)
        return lanes, rows, hist

    monkeypatch.setattr(engine, "batch_lanes_step", unchanged)
    monkeypatch.setattr(engine, "lane_step",
                        lambda f, vg, strategy, opts, lane: lane)


def half(monkeypatch):
    zeus = importlib.import_module("repro.core.zeus")
    from repro.core.engine import CONVERGED

    solve, select = zeus.solve_phase2, zeus._select_best

    def first_half(res):
        b = res.x.shape[0] // 2
        return res._replace(x=res.x[:b], fval=res.fval[:b],
                            status=res.status[:b])

    def counted_on_half(*args, **kwargs):
        res = solve(*args, **kwargs)
        return res._replace(n_converged=jnp.sum(
            first_half(res).status == CONVERGED))

    monkeypatch.setattr(zeus, "solve_phase2", counted_on_half)
    monkeypatch.setattr(zeus, "_select_best",
                        lambda res: select(first_half(res)))


def skip_half(monkeypatch):
    from repro.core import engine

    step = engine.batch_lanes_step

    def half_stepped(bobj, bstrategy, opts, lanes):
        new, rows, hist = step(bobj, bstrategy, opts, lanes)
        on = jnp.arange(lanes.x.shape[0]) < lanes.x.shape[0] // 2

        def keep(n, o):
            return jnp.where(on.reshape(on.shape + (1,) * (n.ndim - 1)), n, o)

        return jax.tree.map(keep, new, lanes), rows, hist

    monkeypatch.setattr(engine, "batch_lanes_step", half_stepped)


def early_stop(monkeypatch):
    zeus = importlib.import_module("repro.core.zeus")

    setup = zeus.phase2_setup

    def halved(opts):
        strategy, eopts = setup(opts)
        return strategy, dataclasses.replace(eopts,
                                             iter_max=eopts.iter_max // 2)

    monkeypatch.setattr(zeus, "phase2_setup", halved)


def altered(monkeypatch):
    zeus = importlib.import_module("repro.core.zeus")

    select = zeus._select_best
    monkeypatch.setattr(zeus, "_select_best",
                        lambda res: (lambda x, f: (x + 0.05, f))(*select(res)))


def _toys_altered(monkeypatch, alter):
    split = harness.split_toys
    monkeypatch.setattr(harness, "split_toys",
                        lambda a, toys: alter(split(a, toys)))


def shifted_toys(monkeypatch, cfg):
    _toys_altered(monkeypatch, lambda toys: toys[1:] + toys[:1])


def raised_sweeps(monkeypatch, cfg):
    vg = spec.problem_module(cfg).vg_cost(cfg)

    def raised(toys):
        sweeps = [int(t["iterations"]) for t in toys]
        toy = toys[int(np.argmin(sweeps))]
        # a per-lane sweep tries 1 to 20 rungs, so a lane's counter admits
        # a range of sweeps: the raise is past what every lane still
        # active admits, at least to the batch's most
        most = work.sweep_range(cfg, toy["n_evals"], vg)[1]
        live = toy["status"] == reference.STOPPED
        toy["iterations"] = np.int32(max(max(sweeps), most[live].min() + 1))
        return toys

    _toys_altered(monkeypatch, raised)


def neighbour_pso(monkeypatch, cfg):
    _toys_altered(monkeypatch, lambda toys: [
        dict(t, pso_best_f=toys[(i + 1) % len(toys)]["pso_best_f"])
        for i, t in enumerate(toys)])


def f32_build(cell, monkeypatch):
    cell["cfg"]["dtype"] = "float32"


def f32_replay(cell, monkeypatch):
    draw = reference.pso_draws
    monkeypatch.setattr(reference, "pso_draws", lambda raw_key, cfg: draw(
        raw_key, dict(cfg, dtype="float32")))


def _run(name, cell_fault=None, monkeypatch=None):
    cell = _tiny.tiny_cell(name)
    if cell_fault is not None:
        cell_fault(cell, monkeypatch)
    return harness.run(cell, SEED, 0.5, False, time.perf_counter(),
                       require_kernel=False, log=lambda m: None)


@pytest.fixture(autouse=True)
def _jnp_kernels(monkeypatch):
    monkeypatch.setenv("REPRO_DISABLE_PALLAS", "1")


@pytest.mark.parametrize("name", sorted(_tiny.SIZES))
def test_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1


CAUGHT = {"half": "count_gap", "skip_half": "sweep_gap",
          "early_stop": "stop_gap", "altered": "best_gap",
          "shifted_toys": "fval_gap", "raised_sweeps": "stop_gap",
          "neighbour_pso": "pso_gap"}
PER_LANE = {"skip_half", "early_stop"}  # faults a per-lane cell cannot have
TOYS = {"shifted_toys", "raised_sweeps", "neighbour_pso"}  # many toys only


def _faults():
    for name in sorted(_tiny.SIZES):
        cfg = _tiny.tiny_cell(name)["cfg"]
        per_lane = work.sweep_mode(cfg) == "per_lane"
        for fault in (frozen, half, skip_half, early_stop, altered,
                      shifted_toys, raised_sweeps, neighbour_pso):
            if per_lane and fault.__name__ in PER_LANE:
                continue
            if "toys_per_solve" not in cfg and fault.__name__ in TOYS:
                continue
            yield pytest.param(name, fault, id=f"{name}-{fault.__name__}")


@pytest.mark.parametrize("name,fault", list(_faults()))
def test_broken_solve_is_not_correct(name, fault, monkeypatch):
    if fault.__name__ in TOYS:
        fault(monkeypatch, _tiny.tiny_cell(name)["cfg"])
    else:
        fault(monkeypatch)
    out = _run(name)
    assert not out["correct"], out["checks"]
    if fault is frozen:  # no lane converges: every solve fails
        assert out["failed"] == out["attempted"]
    else:
        c = out["checks"][CAUGHT[fault.__name__]]
        assert c["value"] > c["limit"], out["checks"]


FLOAT64 = sorted(n for n in _tiny.SIZES
                 if _tiny.tiny_cell(n)["cfg"]["dtype"] == "float64")


@pytest.mark.parametrize("fault", [f32_build, f32_replay],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", FLOAT64)
def test_float64_cell_refuses_float32(name, fault, monkeypatch):
    """A float32 build of a float64 cell fails its limits (its gradient and
    values are off by float32's rounding, its swarm by float32's draws), and
    so does a float64 program checked against a float32 swarm."""
    out = _run(name, fault, monkeypatch)
    assert not out["correct"] and out["failed"] == 0, out["checks"]
    over = {k for k in reference.NAMES
            if out["checks"][k]["value"] > out["checks"][k]["limit"]}
    # float32 rounds the swarm's best and the finale's value alike; a swarm
    # drawn apart moves the swarm's best
    assert over & ({"pso_gap", "best_gap"} if fault is f32_build
                   else {"pso_gap"}), out["checks"]
