"""The one traffic generator: a mix file's parameters, drawn from the seed.

Solve i of a run gets PRNG key data and, where the mix sends data, its own
pseudo-dataset, both drawn from (seed, i) alone, so the same seed gives the
same stream and solve i is the same whatever ran before it. Everything is
drawn in set-up into a pool of `pool` items; a window that outruns the pool
starts it again from item 0.

Mix keys: `pool`, `check_sample` (how many finished solves the check
compares, drawn from the seed) and `data` (whether each solve sends the
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
        self.pool = int(mix["pool"])
        self.check_sample = int(mix["check_sample"])
        self.keys = np.stack([_key(seed, _TIMED, i) for i in range(self.pool)])
        self.data = ([self._data(_DATA, i) for i in range(self.pool)]
                     if self.sends_data else None)

    def _data(self, stream, i):
        return self.problem.make_data(
            self.cfg, np.random.default_rng(_seq(self.seed, stream, i)))

    def args(self, i: int) -> tuple:
        """Host arrays of timed solve i: (key data[, dataset])."""
        j = i % self.pool
        return (self.keys[j],) + ((self.data[j],) if self.sends_data else ())

    def data_of(self, i: int):
        return self.data[i % self.pool] if self.sends_data else None

    def warmup_args(self, j: int) -> tuple:
        """Inputs of warm-up solve j, apart from the timed stream."""
        key = _key(self.seed, _WARMUP, j)
        return (key,) + ((self._data(_WARMUP, j),) if self.sends_data else ())

    def sample(self, n_done: int) -> list:
        """Indices of the finished solves the check compares."""
        k = min(n_done, self.check_sample)
        rng = np.random.default_rng(_seq(self.seed, 3, n_done))
        return sorted(rng.choice(n_done, size=k, replace=False).tolist())
