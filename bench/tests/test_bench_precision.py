"""The precision a configuration states and the kernels it names: JAX's
64-bit mode is on exactly inside a float64 run and restored after it; the
reference draws the swarm in the configuration's dtype, bit for bit as the
program does; a compiled solve that lacks a listed Pallas kernel is
refused."""
import time

import _tiny
import jax
import numpy as np
import pytest

import harness
import reference
import spec
from stream import Stream

SEED = 2**31 + 7


@pytest.fixture(autouse=True)
def _jnp_kernels(monkeypatch):
    monkeypatch.setenv("REPRO_DISABLE_PALLAS", "1")


@pytest.mark.parametrize("name", sorted(_tiny.SIZES))
def test_x64_is_on_only_inside_a_float64_run(name, monkeypatch):
    cell = _tiny.tiny_cell(name)
    want = cell["cfg"]["dtype"] == "float64"
    seen = []
    compile_solve, drive = harness.compile_solve, harness.drive

    def compiling(solve, args, kernels=()):
        seen.append(("compile", jax.config.jax_enable_x64))
        seen.append(("data", [np.asarray(a).dtype for a in args[1:]]))
        return compile_solve(solve, args, kernels)

    def driving(*args):
        seen.append(("window", jax.config.jax_enable_x64))
        return drive(*args)

    monkeypatch.setattr(harness, "compile_solve", compiling)
    monkeypatch.setattr(harness, "drive", driving)
    before = jax.config.jax_enable_x64
    out = harness.run(cell, SEED, 0.3, False, time.perf_counter(),
                      require_kernel=False, log=lambda m: None)
    assert out["correct"], out["checks"]
    assert jax.config.jax_enable_x64 == before
    data = [np.dtype(cell["cfg"]["dtype"])] if cell["mix"]["data"] else []
    assert seen == [("compile", want), ("data", data), ("window", want)]


@pytest.mark.parametrize("name", sorted(_tiny.SIZES))
def test_replay_draws_the_programs_swarm_at_init(name):
    from repro.core.pso import init_swarm

    cfg = _tiny.tiny_cell(name)["cfg"]
    cfg["zeus"]["pso"]["n_particles"] = 64
    problem = spec.problem_module(cfg)
    raw = Stream({"pool": 4, "check_sample": 1, "data": False}, cfg,
                 problem, SEED).key_of(0)
    with harness.precision(cfg):
        x, v = reference.pso_draws(raw, cfg)[:2]
        swarm = init_swarm(lambda z: z.sum(), jax.random.wrap_key_data(raw),
                           64, cfg["dim"], cfg["lower"], cfg["upper"],
                           dtype=np.dtype(cfg["dtype"]))
    assert x.dtype == v.dtype == np.dtype(cfg["dtype"])
    np.testing.assert_array_equal(x, np.asarray(swarm.x))
    np.testing.assert_array_equal(v, np.asarray(swarm.v))


def test_a_listed_kernel_the_solve_lacks_is_refused(monkeypatch):
    import trace_reduce

    args = (np.zeros(3, np.float32),)
    harness.compile_solve(lambda x: x * 2.0, args, ())
    monkeypatch.setattr(trace_reduce, "kernel_names",
                        lambda text: {"custom-call.1": "_value_kernel"})
    harness.compile_solve(lambda x: x * 3.0, args, ["_value_kernel"])
    with pytest.raises(RuntimeError, match=r"\['_pso_kernel'\]"):
        harness.compile_solve(lambda x: x * 4.0, args,
                              ["_value_kernel", "_pso_kernel"])


def test_a_run_checks_the_configurations_kernels():
    """On the CPU no Pallas kernel compiles to a tpu_custom_call: a
    configuration that lists one is refused, one that lists none runs."""
    cell = _tiny.tiny_cell("dijet-fit.toys")
    assert cell["cfg"]["kernels"] == []
    out = harness.run(cell, SEED, 0.3, False, time.perf_counter(),
                      log=lambda m: None)
    assert out["correct"], out["checks"]
    cell["cfg"]["kernels"] = ["_pso_kernel"]
    with pytest.raises(RuntimeError, match="_pso_kernel"):
        harness.run(cell, SEED, 0.3, False, time.perf_counter(),
                    log=lambda m: None)

