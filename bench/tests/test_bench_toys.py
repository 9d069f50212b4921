"""A solve that carries many toys: jax.vmap(zeus) over T toys answers each
toy as its own solve does; toy j's inputs are the same whatever a solve
carries, and a configuration without `toys_per_solve` streams what it did;
the check compares, in the slowest solve, the toy with the most sweeps;
`toy_sweep_share` reads the toys' sweep counts."""
import types

import _paths  # noqa: F401
import _tiny
import numpy as np
import pytest

import harness
import spec
from reference import CONVERGED
from stream import Stream

SEED = 2**31 + 13
TOYS = 4


@pytest.fixture(autouse=True)
def _jnp_kernels(monkeypatch):
    monkeypatch.setenv("REPRO_DISABLE_PALLAS", "1")


def _dijet(toys=TOYS):
    cell = _tiny.tiny_cell("dijet-fit.toys")
    cfg = dict(cell["cfg"])
    if toys is None:
        del cfg["toys_per_solve"]
    else:
        cfg["toys_per_solve"] = toys
    return cell, cfg, spec.problem_module(cfg)


def _answers(cfg, problem, stream, i):
    """Toy answers of solve i of `stream`, compiled and run on the host."""
    with harness.precision(cfg):
        args = stream.args(i)
        exe, _ = harness.compile_solve(harness.solve_program(cfg, problem),
                                       args)
        return harness.split_toys(harness.answer_of(exe(*args)), stream.toys)


def _runaway(a):
    return ~(np.isfinite(a["fval"]) & np.isfinite(a["grad_norm"]))


def test_a_batched_solve_answers_each_toy_as_its_own_solve():
    """The same sweeps, converged lanes and best to float64 rounding. A
    lane that runs off towards overflow (|x| past 1e9, chaotic) may end
    DIVERGED in one program and STOPPED in the other, as the two round it
    differently on its way out; it is never the best."""
    cell, cfg, problem = _dijet()
    _, one_cfg, _ = _dijet(None)
    toys = _answers(cfg, problem, Stream(cell["mix"], cfg, problem, SEED), 1)
    alone = Stream(cell["mix"], one_cfg, problem, SEED)
    assert len(toys) == TOYS
    for t, a in enumerate(toys):
        b = _answers(one_cfg, problem, alone, TOYS + t)[0]
        assert int(a["iterations"]) == int(b["iterations"])
        assert int(a["n_converged"]) == int(b["n_converged"])
        np.testing.assert_array_equal(a["status"] == CONVERGED,
                                      b["status"] == CONVERGED)
        apart = a["status"] != b["status"]
        assert not np.any(apart & ~(_runaway(a) | _runaway(b)))
        np.testing.assert_allclose(a["best_f"], b["best_f"], rtol=1e-15)
        np.testing.assert_allclose(a["best_x"], b["best_x"], rtol=1e-9)
        assert a["best_f"].dtype == np.float64


def _golden(seed, problem, cfg, j):
    """Toy j as the stream drew timed solve j before solves carried toys."""
    seq = lambda s: np.random.SeedSequence([seed & (2**64 - 1), s, j])
    return (seq(0).generate_state(2, np.uint32),
            problem.make_data(cfg, np.random.default_rng(seq(1))))


def test_toy_j_is_the_same_whatever_a_solve_carries():
    cell, cfg, problem = _dijet()
    mix = dict(cell["mix"], pool=3 * TOYS)
    none = Stream(mix, _dijet(None)[1], problem, SEED)
    one = Stream(mix, dict(cfg, toys_per_solve=1), problem, SEED)
    four = Stream(mix, cfg, problem, SEED)
    for j in range(3 * TOYS + 2):  # past the pool's end too
        key, data = _golden(SEED, problem, cfg, j % mix["pool"])
        # without the key: today's stream, bit for bit and unstacked
        got = none.args(j)
        assert got[0].shape == (2,) and got[1].shape == data.shape
        np.testing.assert_array_equal(got[0], key)
        np.testing.assert_array_equal(got[1], data)
        for s in (one, four):
            i, t = divmod(j, s.per_solve)
            got = s.args(i)
            assert got[0].shape == (s.per_solve, 2)
            np.testing.assert_array_equal(got[0][t], key)
            np.testing.assert_array_equal(got[1][t], data)
            np.testing.assert_array_equal(s.key_of(j), key)
            np.testing.assert_array_equal(s.data_of(j), data)
    with pytest.raises(ValueError, match="whole number of solves"):
        Stream(dict(mix, pool=TOYS + 1), cfg, problem, SEED)


def test_the_check_takes_the_slowest_solves_longest_toy():
    stream = types.SimpleNamespace(per_solve=3, sample=lambda n: [0, 1])
    win = harness.Window(latencies=[0.1, 0.5, 0.2], outputs=None,
                         elapsed=0.8, t_first=0.0)
    answers = [{"iterations": k} for k in (9, 9, 9, 4, 11, 7, 20, 1, 1)]
    assert harness.sample(stream, win, answers) == [0, 1, 4]
    answers[3:6] = [None] * 3  # the slowest solve raised
    assert harness.sample(stream, win, answers) == [0, 1, 3]


def _ctx(cfg, sweeps):
    return harness.Context(cfg=cfg, trace=None, problem=None,
                           answers=[{"iterations": np.int32(k)}
                                    for k in sweeps],
                           device_kind="TPU v5 lite")


def test_toy_sweep_share_by_hand():
    read = spec.metric_reader("toy_sweep_share").read
    # solves (13, 15, 14, 14) and (10, 10, 10, 10): 96 toy-sweeps of 100
    assert read(_ctx({"toys_per_solve": 4},
                     [13, 15, 14, 14, 10, 10, 10, 10])) == pytest.approx(0.96)
    assert read(_ctx({"toys_per_solve": 2}, [0, 0])) is None
    assert read(_ctx({"toys_per_solve": 2}, [])) is None
    assert read(_ctx({}, [3, 4])) is None  # a solve is one toy


def test_toy_sweep_share_of_a_recorded_solve():
    cell, cfg, problem = _dijet()
    toys = _answers(cfg, problem, Stream(cell["mix"], cfg, problem, SEED), 0)
    sweeps = [int(a["iterations"]) for a in toys]
    ctx = harness.Context(cfg=cfg, trace=None, problem=problem,
                          answers=toys, device_kind="TPU v5 lite")
    share = spec.metric_reader("toy_sweep_share").read(ctx)
    assert share == pytest.approx(sum(sweeps) / (TOYS * max(sweeps)))
    assert 0 < share <= 1
