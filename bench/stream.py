"""The one traffic generator: a mix file's parameters, drawn from the seed.

A run's problems are toys: toy j gets PRNG key data and, where the mix sends
data, its own pseudo-dataset, both drawn from (seed, j) alone, so the same
seed gives the same stream and toy j is the same whatever ran before it and
however many toys a solve carries. Where the configuration sets
`toys_per_solve` T, solve i carries toys iT .. iT + T - 1, its inputs
stacked along a leading axis of T; without it a solve is one toy, with the
toy's own unstacked inputs. Everything is drawn in set-up into a pool of
`pool` toys (a multiple of T); a window that outruns the pool starts it
again from toy 0.

Mix keys: `pool`, `check_sample` (how many finished toys the check
compares, drawn from the seed) and `data` (whether each toy sends the
problem's pseudo-dataset, `problem.make_data(cfg, rng)`). One caller sends
the solves back to back (harness.drive).
"""
from __future__ import annotations

import numpy as np

_TIMED, _DATA, _WARMUP = 0, 1, 2


def _seq(seed: int, stream: int, i: int):
    return np.random.SeedSequence([seed & (2**64 - 1), stream, i])


def _key(seed, stream, i):
    return _seq(seed, stream, i).generate_state(2, np.uint32)


class Stream:
    def __init__(self, mix: dict, cfg: dict, problem, seed: int):
        self.seed, self.cfg, self.problem = seed, cfg, problem
        self.sends_data = bool(mix["data"])
        # None: a solve is one toy, its inputs unstacked
        self.toys = cfg.get("toys_per_solve")
        self.per_solve = self.toys or 1
        self.pool = int(mix["pool"])
        if self.pool % self.per_solve:
            raise ValueError(f"the pool of {self.pool} toys is no whole "
                             f"number of solves of {self.per_solve} toys")
        self.check_sample = int(mix["check_sample"])
        self.keys = self._keys(_TIMED, range(self.pool))
        self.data = (self._data(_DATA, range(self.pool))
                     if self.sends_data else None)

    def _keys(self, stream, toys):
        return np.stack([_key(self.seed, stream, j) for j in toys])

    def _data(self, stream, toys):
        return np.stack([self.problem.make_data(
            self.cfg, np.random.default_rng(_seq(self.seed, stream, j)))
            for j in toys])

    def _solve(self, keys, data, j):
        """The inputs of the solve whose first toy is row j."""
        n = self.toys
        rows = slice(j, j + n) if n else j
        return (keys[rows],) + ((data[rows],) if self.sends_data else ())

    def args(self, i: int) -> tuple:
        """Host arrays of timed solve i: (key data[, dataset]), stacked
        over its toys where the configuration sets toys_per_solve."""
        return self._solve(self.keys, self.data,
                           i * self.per_solve % self.pool)

    def key_of(self, j: int):
        """Key data of timed toy j."""
        return self.keys[j % self.pool]

    def data_of(self, j: int):
        """Dataset of timed toy j, or None where the mix sends none."""
        return self.data[j % self.pool] if self.sends_data else None

    def warmup_args(self, j: int) -> tuple:
        """Inputs of warm-up solve j, apart from the timed stream."""
        toys = range(j * self.per_solve, (j + 1) * self.per_solve)
        return self._solve(self._keys(_WARMUP, toys),
                           self._data(_WARMUP, toys) if self.sends_data
                           else None, 0)

    def sample(self, n_done: int) -> list:
        """Indices of the finished toys the check compares."""
        k = min(n_done, self.check_sample)
        rng = np.random.default_rng(_seq(self.seed, 3, n_done))
        return sorted(rng.choice(n_done, size=k, replace=False).tolist())
