"""A whole run of each cell at a small size on the CPU, with the device
check left out: sound, it comes out correct; with the solve broken
underneath, it comes out not correct, once for each fault a cell can have.

- frozen: the phase-2 sweep returns its lanes unchanged;
- half: half of the lanes left out of the converged count and the finale;
- skip_half: every sweep steps half of the lanes and leaves the rest at
  their swarm start;
- early_stop: the sweep loop stops after half of its sweeps;
- altered: the answer altered where it is produced (best_x moved off the
  best lane).

The cells run on one chip, so there is no exchange between chips to leave
out.
"""
import dataclasses
import importlib
import time

import _tiny
import jax
import jax.numpy as jnp
import pytest

import harness

SEED = 2**31 + 3


def frozen(monkeypatch):
    from repro.core import engine

    step = engine.batch_lanes_step

    def unchanged(bobj, bstrategy, opts, lanes):
        _, rows, hist = step(bobj, bstrategy, opts, lanes)
        return lanes, rows, hist

    monkeypatch.setattr(engine, "batch_lanes_step", unchanged)


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


def _run(name):
    return harness.run(_tiny.tiny_cell(name), SEED, 0.5, False,
                       time.perf_counter(), require_kernel=False,
                       log=lambda m: None)


@pytest.fixture(autouse=True)
def _jnp_kernels(monkeypatch):
    monkeypatch.setenv("REPRO_DISABLE_PALLAS", "1")


@pytest.mark.parametrize("name", sorted(_tiny.SIZES))
def test_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1


CAUGHT = {"half": "count_gap", "skip_half": "sweep_gap",
          "early_stop": "stop_gap", "altered": "best_gap"}


@pytest.mark.parametrize("fault", [frozen, half, skip_half, early_stop, altered],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", sorted(_tiny.SIZES))
def test_broken_solve_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    out = _run(name)
    assert not out["correct"], out["checks"]
    if fault is frozen:  # no lane converges: every solve fails
        assert out["failed"] == out["attempted"]
    else:
        c = out["checks"][CAUGHT[fault.__name__]]
        assert c["value"] > c["limit"], out["checks"]
