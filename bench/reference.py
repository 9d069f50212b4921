"""The plain reference, the comparison that decides `correct`, and its control.

Nothing here imports the program. A toy's answer (one problem's: a solve
carries one toy, or many under jax.vmap) is judged by what it says, layer by
layer, over every lane:

- phase 1: the paper's PSO (w, c1, c2 update, personal and global best by
  strict improvement, first index on ties) is replayed in float64 from the
  solve's own key, with the uniform draws taken from `jax.random` in the
  order the swarm consumes them and in the configuration's dtype, as the
  program draws them; `pso_gap` is the relative gap between the
  swarm's best value the solve reports and the replay's;
- phase 2, every lane: its reported value must be the float64 value at its
  iterate (`fval_gap`), and every lane the solve calls converged must be a
  stationary point by the float64 gradient (`conv_grad`, the largest norm);
- the sweep loop, exactly: each lane's status follows from its state and the
  sweeps taken, and no lane reports a non-finite gradient norm where the
  float64 gradient is far from overflow (`status_gap`; lanes near it are
  judged by their status and counted as `overflow_lanes`), each lane still active at the end was active
  in every sweep taken, by the sweeps its objective-eval counter admits on
  the configuration's path (work.sweep_range; `sweep_gap`), the
  loop stopped where the stop rule says, no earlier and no later
  (`stop_gap`), and the converged count is the number of converged statuses
  (`count_gap`);
- finale: `best_f` must be the float64 value at `best_x`, and that the least
  float64 value over the converged lanes (`best_gap`).

A run reads `pso_gap` as the median over the toys it compares, the other
numbers as their worst (`AGGREGATE`).

The control of a float32 configuration puts this reference in the
program's place at the nearest precision below float32, bfloat16: the
iterates as a bfloat16 solve holds them, their values and the best chosen in
bfloat16, and the swarm replayed in bfloat16; the loop's counters stay the
program's. A float64 configuration's control is the program's own float32
path (bench/calibrate.py).
"""
from __future__ import annotations

import functools
import statistics

import numpy as np

import work

NAMES = ("pso_gap", "conv_grad", "fval_gap", "best_gap",
         "status_gap", "sweep_gap", "stop_gap", "count_gap")
# reported beside them with no limit: the lanes whose failure the reference
# could not judge (failed_lanes), so that a rise shows
COUNTED = ("overflow_lanes",)
# the program's BFGSResult.status codes
DIVERGED, CONVERGED, STOPPED = 0, 1, 2


@functools.lru_cache(maxsize=None)
def _draw_fn(n, dim, iters, lower, upper, dtype):
    import jax
    import jax.numpy as jnp

    def draws(raw_key):
        key = jax.random.wrap_key_data(raw_key)
        kx, kv, key = jax.random.split(key, 3)
        x = jax.random.uniform(kx, (n, dim), dtype, lower, upper)
        span = upper - lower
        v = jax.random.uniform(kv, (n, dim), dtype, -span, span)
        r1, r2 = [], []
        for _ in range(iters):
            k1, k2, key = jax.random.split(key, 3)
            r1.append(jax.random.uniform(k1, (n, dim), dtype))
            r2.append(jax.random.uniform(k2, (n, dim), dtype))
        return x, v, jnp.stack(r1), jnp.stack(r2)

    return jax.jit(draws)


def pso_draws(raw_key, cfg):
    """The uniform draws of the swarm's init and iterations, on the host, in
    the configuration's dtype (a float64 draw needs JAX's 64-bit mode, which
    harness.precision turns on)."""
    import jax

    p = cfg["zeus"]["pso"]
    fn = _draw_fn(p["n_particles"], cfg["dim"], p.get("iter_pso", 5),
                  float(cfg["lower"]), float(cfg["upper"]),
                  np.dtype(cfg["dtype"]))
    return jax.device_get(fn(np.asarray(raw_key, np.uint32)))


def replay_pso(draws, value, cfg, xp=np, dtype=np.float64):
    """The swarm's best value after init and `iter_pso` iterations."""
    p = cfg["zeus"]["pso"]
    w, c1, c2 = p.get("w", 0.5), p.get("c1", 1.2), p.get("c2", 1.5)
    x, v, r1, r2 = (xp.asarray(a, dtype) for a in draws)
    pf = value(x)
    px = x
    i = int(xp.argmin(pf))
    gf, gx = pf[i], x[i]
    for t in range(r1.shape[0]):
        v = w * v + c1 * r1[t] * (px - x) + c2 * r2[t] * (gx[None] - x)
        x = x + v
        if p.get("clip_to_range", False):
            x = xp.clip(x, cfg["lower"], cfg["upper"])
        fv = value(x)
        better = fv < pf
        pf = xp.where(better, fv, pf)
        px = xp.where(better[:, None], x, px)
        i = int(xp.argmin(fv))
        if fv[i] < gf:
            gf, gx = fv[i], x[i]
    return float(gf)


def _rel(a, b):
    return abs(a - b) / max(1.0, abs(b))


def _worst(a) -> float:
    """The largest entry; +inf where one is NaN or there are none."""
    a = np.asarray(a, np.float64)
    return float("inf") if a.size == 0 or np.isnan(a).any() else float(a.max())


# A lane's float64 gradient is near float32's overflow where a component at
# its point reaches this. Float32's norm overflows past ~1.8e19 per
# component, and the sums that make a gradient run some hundreds of times
# its components (the dijet NLL sums 40 bins of mu times d log mu, up to ~7);
# float64 on a TPU is pairs of float32 and keeps float32's exponent range.
NEAR_OVERFLOW = 1e18


def failed_lanes(ans, grad_at):
    """(failed, deferred, wrong), a boolean per lane: the lanes that failed
    by the sweep loop's rule (a value or a gradient component not finite),
    as far as the answer shows it.

    The answer carries the gradient's norm, not its components. Where a
    lane that did not converge reports a finite value and a norm that is
    not finite, the reference takes its own float64 gradient at the lane's
    point (`grad_at`, rows to rows):
    - every component finite and under NEAR_OVERFLOW: no sound program's
      gradient overflows there, so the norm is wrong (`wrong`, which
      status_gap counts) and the lane counts as failed, as its norm says;
    - else the device's arithmetic may overflow where the reference's does
      not (the norm past ~1.3e154 in float64 on the CPU, past ~1.8e19 in
      float32 and in the float64 XLA emulates on a TPU, where it reads NaN),
      and the reference cannot redo it: the lane counts as failed where the
      program calls it DIVERGED and as active where it calls it STOPPED
      (`deferred`, counted in the checks as `overflow_lanes`); loop_gaps
      then holds its counter to that."""
    status = np.asarray(ans["status"])
    fval = np.asarray(ans["fval"], np.float64)
    norm = np.asarray(ans["grad_norm"], np.float64)
    failed = (status != CONVERGED) & ~(np.isfinite(fval) & np.isfinite(norm))
    deferred = np.zeros_like(failed)
    wrong = failed & np.isfinite(fval)
    unsure = np.flatnonzero(wrong)
    if unsure.size:
        with np.errstate(all="ignore"):
            g = np.asarray(grad_at(np.asarray(ans["x"], np.float64)[unsure]),
                           np.float64)
        near = unsure[~np.all(np.abs(g) < NEAR_OVERFLOW, axis=-1)]
        deferred[near] = True
        wrong[near] = False
        failed[near] = status[near] == DIVERGED
    return failed, deferred, wrong


def loop_gaps(ans, cfg, vg_cost: int, failed):
    """(status_gap, sweep_gap, stop_gap): the lanes and stop-rule steps that
    disagree with the sweep loop's rules, counted exactly.

    A lane is active from its start until it converges or fails
    (`failed`, failed_lanes), and every sweep adds its rungs and
    `vg_cost` to its eval counter, so its active sweeps s_i lie in
    `work.sweep_range` (exactly known on a whole-ladder path); a step is
    counted only where no s_i in range agrees with the rule.
    The loop runs another sweep while fewer than iter_bfgs have run, fewer
    than required_c (default: every lane) lanes have converged, and some
    lane is active.
    At the end a lane neither converged nor failed is DIVERGED after the
    last allowed sweep and STOPPED before it; a failed lane is DIVERGED."""
    b = cfg["zeus"]["bfgs"]
    kmax = b["iter_bfgs"]
    status = np.asarray(ans["status"])
    k = int(ans["iterations"])
    conv = status == CONVERGED
    live = ~conv & ~failed
    need = b.get("required_c") or status.size
    want = np.where(failed | (k >= kmax), DIVERGED, STOPPED)
    status_gap = int(np.sum(~conv & (status != want)))
    r = work.sweep_range(cfg, ans["n_evals"], vg_cost)
    if r is None:  # the counter does not decode: no lane's sweeps are known
        return status_gap, int(status.size), 1
    least, most = r
    sweep_gap = int(np.sum(least > k)
                    + np.sum(live & ((least > k) | (most < k))))
    stop_gap = int(k > kmax)
    # stopped early: sweeps left, too few converged and a lane still active
    stop_gap += int(k < kmax and conv.sum() < need and live.any())
    # stopped late: before sweep k, enough had converged or none was active
    if k >= 1:
        stop_gap += int(np.sum(conv & (most <= k - 1)) >= need
                        or not np.any(most >= k))
    return status_gap, sweep_gap, stop_gap


def readings(ans, problem, cfg, data, pso_ref):
    """The numbers compared for one solve.

    ans: the solve's answer on the host (x, fval, grad_norm, status,
    n_evals, iterations, n_converged, best_x, best_f, pso_best_f); pso_ref:
    the float64 replay's swarm best. Returns (numbers,
    why_failed), why_failed None where the solve gave an answer to compare."""
    best_f = float(ans["best_f"])
    conv = np.asarray(ans["status"]) == CONVERGED
    if not np.isfinite(best_f):
        return None, "non-finite best_f"
    if not conv.any():
        return None, "no converged lane"
    x = np.asarray(ans["x"], np.float64)
    fval = np.asarray(ans["fval"], np.float64)
    fin = np.isfinite(fval) & np.isfinite(np.asarray(ans["grad_norm"]))
    f64 = problem.value(x[fin], data, cfg)
    on = conv[fin]
    g64 = problem.grad(x[conv], data, cfg)
    fmin = float(np.min(f64[on]))
    fb = float(problem.value(np.asarray(ans["best_x"], np.float64)[None],
                             data, cfg)[0])
    failed, deferred, wrong = failed_lanes(
        ans, lambda z: problem.grad(z, data, cfg))
    status_gap, sweep_gap, stop_gap = loop_gaps(ans, cfg, problem.vg_cost(cfg),
                                                failed)
    return {
        "pso_gap": _rel(float(ans["pso_best_f"]), pso_ref),
        "conv_grad": _worst(np.linalg.norm(g64, axis=-1)),
        "fval_gap": _worst(np.abs(fval[fin] - f64) / np.maximum(1.0, np.abs(f64))),
        "best_gap": max(_rel(best_f, fb), (fb - fmin) / max(1.0, abs(fmin))),
        "status_gap": float(status_gap + wrong.sum()),
        "sweep_gap": float(sweep_gap),
        "stop_gap": float(stop_gap),
        "count_gap": float(abs(int(ans["n_converged"]) - int(conv.sum()))),
        "overflow_lanes": float(deferred.sum()),
    }, None


def control_answer(ans, problem, cfg, data, draws):
    """The answer the reference gives in bfloat16 in the program's place, at
    the same solve: its iterates held in bfloat16, their values and the best
    among the converged in bfloat16, its swarm replayed in bfloat16; the
    statuses and counters are the program's."""
    import jax
    import jax.numpy as jnp

    lo = jnp.bfloat16
    conv = jnp.asarray(np.asarray(ans["status"]) == CONVERGED)
    with jax.default_matmul_precision("bfloat16"):
        x = jnp.asarray(np.asarray(ans["x"], np.float32), lo)
        d = None if data is None else jnp.asarray(data, lo)
        fval = problem.value(x, d, cfg, xp=jnp)
        fc = jnp.where(conv, fval, jnp.inf)
        i = int(jnp.argmin(fc))
        pso = replay_pso(draws, lambda z: problem.value(z, d, cfg, xp=jnp),
                                cfg, xp=jnp, dtype=lo)
    f32 = lambda a: np.asarray(jnp.asarray(a, jnp.float32), np.float64)
    return dict(ans, x=f32(x), fval=f32(fval), best_x=f32(x[i]),
                best_f=float(f32(fc[i])), pso_best_f=pso)


# How a run reads each number over the solves it compares: the worst solve,
# except `pso_gap`, the median. The float64 replay of the swarm takes the
# other branch where a float32 best-so-far comparison is within rounding,
# and the swarm then runs elsewhere (seen on a dijet NLL fit on one v5e: 1
# fit in ~7700 read 1.2e-3 against <= 4e-7 for all others); the median over
# a run's solves is steady, and a fault in phase 1 moves every solve.
AGGREGATE = {"pso_gap": statistics.median, "overflow_lanes": sum}


def aggregate(per_solve: list) -> dict:
    return {k: float(AGGREGATE.get(k, max)([r[k] for r in per_solve]))
            for k in NAMES + COUNTED} if per_solve else {}


def judge(per_solve: list, limits: dict):
    """The run's reading of each number (`aggregate`) beside its limit (a
    COUNTED number's limit is None); ok where every number of NAMES is
    within its limit."""
    worst = aggregate(per_solve)
    checks = {k: {"value": worst[k], "limit": limits[k] if k in NAMES
                  else None} for k in worst}
    ok = bool(per_solve) and all(worst[k] <= limits[k] for k in NAMES)
    return ok, checks
