"""Every cell of BENCHMARK.json, and each held cell, loads by name and builds its configuration
and traffic from a seed; the file keeps to its contract; the measured path
refuses a CPU device."""
import json
import os
import re
import subprocess
import sys

import _paths  # noqa: F401
import _tiny
import numpy as np
import pytest

import harness
import spec
from stream import Stream

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SEED = 2**31 + 11  # run seeds may pass 32 signed bits


@pytest.mark.parametrize("name", CELLS + sorted(_tiny.HELD))
def test_cell_builds_from_its_files(name):
    cell = _tiny.held_cell(name) if name in _tiny.HELD else spec.load_cell(name)
    cfg = cell["cfg"]
    opts = harness.zeus_options(cfg)
    assert opts.pso.n_particles == cfg["zeus"]["pso"]["n_particles"]
    assert opts.bfgs.theta == cfg["zeus"]["bfgs"]["theta"]
    assert cell["mix"]["pool"] % cfg.get("toys_per_solve", 1) == 0
    problem = spec.problem_module(cfg)
    toys = cfg.get("toys_per_solve")
    stream = Stream(dict(cell["mix"], pool=4 * (toys or 1)), cfg, problem,
                    SEED)
    lead = (toys,) if toys else ()
    for i in range(3):
        args = stream.args(i)
        assert args[0].shape == lead + (2,) and args[0].dtype == np.uint32
        assert len(args) == 1 + bool(cell["mix"]["data"])
        assert all(a.shape[:len(lead)] == lead for a in args)
    assert not np.array_equal(stream.args(0)[0], stream.args(1)[0])
    assert set(cfg["limits"]) == set(harness.reference.NAMES)
    for m in cell["per_layer"]:
        assert hasattr(spec.metric_reader(m["name"]), "read")
    assert {"solve_s", "setup_s"} <= {m["name"] for m in cell["end_to_end"]}


class _Counts:
    """A problem that sends a pseudo-dataset with each solve."""

    @staticmethod
    def make_data(cfg, rng):
        return rng.poisson(50.0, size=8).astype(np.float32)


def test_datasets_differ_by_index_and_repeat_for_a_seed():
    mix = {"pool": 3, "check_sample": 2, "data": True}
    cfg = {}
    a, b = Stream(mix, cfg, _Counts, SEED), Stream(mix, cfg, _Counts, SEED)
    other = Stream(mix, cfg, _Counts, SEED + 1)
    d = [a.args(i)[1] for i in range(3)]
    assert d[0].shape == (8,) and d[0].dtype == np.float32
    assert not np.array_equal(d[0], d[1]) and not np.array_equal(d[1], d[2])
    for i in range(3):
        assert np.array_equal(a.args(i)[1], b.args(i)[1])
        assert np.array_equal(a.args(i)[0], b.args(i)[0])
        assert np.array_equal(a.data_of(i), a.args(i)[1])
    assert not np.array_equal(a.args(0)[1], other.args(0)[1])
    # the pool wraps: solve `pool` sends what solve 0 sent
    assert np.array_equal(a.args(3)[1], d[0])
    # warm-up inputs are apart from the timed stream
    assert not np.array_equal(a.warmup_args(0)[0], a.args(0)[0])


def test_check_sample_repeats_for_a_seed():
    mix = {"pool": 4, "check_sample": 5, "data": False}
    a, b = Stream(mix, {}, _Counts, SEED), Stream(mix, {}, _Counts, SEED)
    assert a.sample(40) == b.sample(40)
    assert len(a.sample(40)) == 5 and len(set(a.sample(40))) == 5
    assert a.sample(3) == [0, 1, 2]


def test_benchmark_json_keeps_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"] and 1 <= BENCH["run_seconds"] <= 51
    configs = {c["name"]: c for c in BENCH["configs"]}
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for item in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(item["name"]), item["name"]
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and os.path.exists(
            spec.ROOT / c["file"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (spec.BENCH / "traffic" / f"{w['traffic']}.json").exists()
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert (spec.BENCH / "metrics" / f"{m['name']}.py").exists()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_measured_path_refuses_a_cpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_compile_counter_sees_a_compile_only_while_armed():
    import jax
    import jax.numpy as jnp

    counter = harness.CompileCounter()
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(3))
    assert counter.events == []
    counter.armed = True
    jax.jit(lambda x: x * 5.0 - 2.0)(jnp.ones(7))
    counter.armed = False
    counter.close()
    assert any(e.startswith("/jax/core/compile") for e in counter.events)


def test_solve_runs_on_one_chip_and_its_footprint_is_read():
    import jax
    import jax.numpy as jnp

    exe = jax.jit(lambda x: jnp.outer(x, x).sum(0)).lower(
        jax.ShapeDtypeStruct((64,), jnp.float32)).compile()
    assert harness.chips_of(exe) == [jax.devices()[0].id]
    # the argument and the output at least
    assert harness.program_bytes(exe) >= 2 * 64 * 4
