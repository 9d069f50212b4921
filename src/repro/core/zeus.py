"""The ZEUS driver (paper Alg. 1 sequential / Alg. 7 parallel).

Phase 1: PSO improves N random starting points (skipped entirely when
`use_pso=False` — "randomness improved by PSO" is an *option*, §III-A2).
Phase 2: multistart quasi-Newton from the swarm via the unified engine
(core/engine.py); `solver="bfgs"|"lbfgs"` selects the direction strategy by
name from the solver registry, `lane_chunk=C` bounds phase-2 transient
memory to O(C·D²) via chunked lane execution.
Finale:  parallel reduction for the best converged iterate (Alg. 7 line 10)
plus the §VII-B confidence clustering, realized in core/clustering.py.

Each stage traces under a `jax.named_scope` (`zeus.phase1`, `zeus.phase2`
and its sweep stages `zeus.phase2.<stage>`, `zeus.finale`), so every op of
the compiled solve carries its stage in its `op_name` (DESIGN.md §19).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as engine_mod
from repro.core.bfgs import BFGSOptions, BFGSResult, serial_bfgs
from repro.core.engine import CONVERGED, get_solver, run_multistart
from repro.core.lbfgs import LBFGSOptions
from repro.core.meanfield import MeanFieldPSOOptions, run_meanfield_pso
from repro.core.pso import PSOOptions, run_pso, sequential_pso

PHASE1_STRATEGIES = ("pso", "meanfield")


@dataclasses.dataclass(frozen=True)
class ZeusOptions:
    pso: PSOOptions = PSOOptions()
    bfgs: BFGSOptions = BFGSOptions()
    lbfgs: Optional[LBFGSOptions] = None  # back-compat: set => solver="lbfgs"
    use_pso: bool = True
    # phase-1 strategy: "pso" (paper Algs. 8/9, per-particle bests) or
    # "meanfield" (softmax-consensus swarm, core/meanfield.py — scales to
    # 10^6+ particles; configure via `meanfield`). use_pso=False skips
    # phase 1 entirely regardless of this choice.
    phase1: str = "pso"
    meanfield: MeanFieldPSOOptions = MeanFieldPSOOptions()
    dtype: str = "float32"
    solver: str = "bfgs"  # phase-2 strategy name in the engine registry
    lane_chunk: Optional[int] = None  # overrides the solver opts' lane_chunk
    # overrides the solver opts' sweep_mode ("per_lane" | "batched" |
    # "megakernel"); named objectives (obj.fn from the registry)
    # automatically pick the fused value+grad kernels on the batched path
    # and the fused sweep kernel on the megakernel path
    sweep_mode: Optional[str] = None
    # overrides the solver opts' active-lane compaction cadence (batched
    # sweeps only; 0 = off) — see core/engine.py "Active-lane compaction"
    compact_every: Optional[int] = None
    # overrides the solver opts' global cross-chunk lane repacking cadence
    # (batched + lane_chunk only; 0 = off) — see core/engine.py "Global
    # cross-chunk lane repacking"
    repack_every: Optional[int] = None
    # overrides the solver opts' speculative Armijo ladder length (batched
    # only; 0 = full ladder) — see core/engine.py "Adaptive speculative
    # ladder"
    ladder_len: Optional[int] = None
    # overrides the solver opts' sweep schedule ("static" | "auto" |
    # "replay") and controller window — see core/engine.py
    # "Auto-scheduling controller"
    schedule: Optional[str] = None
    schedule_every: Optional[int] = None
    # replay-forced plan indices (with schedule="replay")
    schedule_plans: Optional[tuple] = None
    # overrides the solver opts' telemetry cost-model knobs (engine;
    # DESIGN.md §17): score schedule="auto" plans in measured seconds at
    # host boundaries; telemetry_costs=(c_row, c_launch) fixes the costs
    auto_cost_model: Optional[bool] = None
    telemetry_costs: Optional[tuple] = None
    telemetry_ema: Optional[float] = None
    # overrides the solver opts' fault-tolerance knobs (engine; DESIGN.md
    # §15): per-lane quarantine/retry budget + re-seed policy, sweep-carry
    # checkpoint cadence/location, deterministic fault injection. The
    # engine's retry_bounds default to this solve's (lower, upper).
    retry_budget: Optional[int] = None
    retry_mode: Optional[str] = None  # "perturb" | "uniform"
    retry_sigma: Optional[float] = None
    checkpoint_every: Optional[int] = None
    checkpoint_dir: Optional[str] = None
    checkpoint_keep: Optional[int] = None
    fault_plan: Optional[object] = None  # launch.faults.FaultPlan


# the quarantine re-seed stream is DERIVED from the solve key (fold_in, not
# split): existing fixed-seed runs keep their exact PSO/starts bits, and
# distributed shards fold their device index on top for per-shard streams
_RETRY_FOLD = 0x7E05  # arbitrary domain-separation tag


class ZeusResult(NamedTuple):
    best_x: jnp.ndarray  # (D,) estimated global minimizer
    best_f: jnp.ndarray  # ()
    raw: BFGSResult  # all lanes (for clustering / diagnostics)
    n_converged: jnp.ndarray
    pso_best_f: jnp.ndarray  # global best after phase 1 (inf if PSO skipped)
    n_failed: Optional[jnp.ndarray] = None  # lanes failed at solve end
    n_restarts: Optional[jnp.ndarray] = None  # (B,) quarantine re-seeds


def phase1_particles(opts: ZeusOptions) -> int:
    """Lane count phase 2 will receive: the active phase-1 strategy's swarm
    size (pso.n_particles or meanfield.n_particles). The distributed driver
    shards this number over the mesh; use_pso=False draws the same count
    uniformly."""
    if opts.phase1 == "meanfield":
        return opts.meanfield.n_particles
    return opts.pso.n_particles


def run_phase1(f, key, dim, lower, upper, opts: ZeusOptions, dtype,
               pmin=None, pmoments=None):
    """Dispatch phase 1: returns (starts, best_f_seen) for phase 2.

    `pmin`/`pmoments` are the cross-device hooks of the respective strategy
    (only the active one is used); None on a single host. use_pso=False
    skips the swarm entirely — no objective evaluations in phase 1."""
    if opts.phase1 not in PHASE1_STRATEGIES:
        raise ValueError(
            f"unknown phase1 strategy {opts.phase1!r}; expected one of "
            f"{PHASE1_STRATEGIES}")
    with jax.named_scope("zeus.phase1"):
        if not opts.use_pso:
            return uniform_starts(
                key, phase1_particles(opts), dim, lower, upper, dtype)
        if opts.phase1 == "meanfield":
            mf = run_meanfield_pso(f, key, dim, lower, upper, opts.meanfield,
                                   pmoments=pmoments, dtype=dtype)
            return mf.x, mf.gf
        swarm = run_pso(f, key, dim, lower, upper, opts.pso, pmin=pmin,
                        dtype=dtype)
        return swarm.x, swarm.gf


def _solver_name(opts: ZeusOptions) -> str:
    # opts.lbfgs predates the registry; setting it keeps selecting L-BFGS
    if opts.lbfgs is not None and opts.solver == "bfgs":
        return "lbfgs"
    return opts.solver


def phase2_setup(opts: ZeusOptions):
    """Resolve the phase-2 (strategy, EngineOptions) pair: registry lookup
    plus the ZeusOptions-level overrides. Shared by solve_phase2, the
    distributed driver (which needs the effective EngineOptions to shape
    its out-specs — e.g. whether a ScheduleTrace will be produced), and the
    solve service (which opens a HostedSolve pool from the same effective
    config a solo solve would run, the root of its parity contract)."""
    name = _solver_name(opts)
    factory = get_solver(name)
    if name == "lbfgs":
        solver_opts = opts.lbfgs
        if solver_opts is None:
            # solver="lbfgs" selected by name alone: inherit the shared
            # driver knobs (budget, stop protocol, line search) from the
            # configured BFGS options instead of silently dropping them;
            # memory/ls_c1/ad_mode keep their L-BFGS-tuned defaults.
            b = opts.bfgs
            solver_opts = LBFGSOptions(
                iter_max=b.iter_bfgs,
                theta=b.theta,
                required_c=b.required_c,
                ls_iters=b.ls_iters,
                linesearch=b.linesearch,
                lane_chunk=b.lane_chunk,
                sweep_mode=b.sweep_mode,
                compact_every=b.compact_every,
                repack_every=b.repack_every,
                ladder_len=b.ladder_len,
                schedule=b.schedule,
                schedule_every=b.schedule_every,
                schedule_plans=b.schedule_plans,
                auto_ladders=b.auto_ladders,
                auto_active_frac=b.auto_active_frac,
                auto_cost_model=b.auto_cost_model,
                telemetry_costs=b.telemetry_costs,
                telemetry_ema=b.telemetry_ema,
                retry_budget=b.retry_budget,
                retry_mode=b.retry_mode,
                retry_sigma=b.retry_sigma,
                retry_bounds=b.retry_bounds,
                checkpoint_every=b.checkpoint_every,
                checkpoint_dir=b.checkpoint_dir,
                checkpoint_keep=b.checkpoint_keep,
                fault_plan=b.fault_plan,
            )
    elif name == "bfgs":
        solver_opts = opts.bfgs
    else:
        solver_opts = None  # third-party registrations use their defaults
    strategy, eopts = factory(solver_opts, lane_chunk=opts.lane_chunk)
    if opts.sweep_mode is not None:
        eopts = dataclasses.replace(eopts, sweep_mode=opts.sweep_mode)
    if opts.compact_every is not None:
        eopts = dataclasses.replace(eopts, compact_every=opts.compact_every)
    if opts.repack_every is not None:
        eopts = dataclasses.replace(eopts, repack_every=opts.repack_every)
    if opts.ladder_len is not None:
        eopts = dataclasses.replace(eopts, ladder_len=opts.ladder_len)
    if opts.schedule is not None:
        eopts = dataclasses.replace(eopts, schedule=opts.schedule)
    if opts.schedule_every is not None:
        eopts = dataclasses.replace(eopts, schedule_every=opts.schedule_every)
    if opts.schedule_plans is not None:
        eopts = dataclasses.replace(eopts, schedule_plans=opts.schedule_plans)
    for field in ("auto_cost_model", "telemetry_costs", "telemetry_ema",
                  "retry_budget", "retry_mode", "retry_sigma",
                  "checkpoint_every", "checkpoint_dir", "checkpoint_keep",
                  "fault_plan"):
        v = getattr(opts, field)
        if v is not None:
            eopts = dataclasses.replace(eopts, **{field: v})
    return strategy, eopts


# back-compat alias (pre-service name; the distributed driver still uses it)
_phase2_setup = phase2_setup


def solve_phase2(f, x0, opts: ZeusOptions, pcount=None, retry_key=None,
                 bounds=None, resume_from=None) -> BFGSResult:
    """Phase 2 through the engine: registry lookup -> run_multistart.

    `bounds=(lower, upper)` backstops the engine's retry_bounds (quarantine
    re-seed box) when the solver opts leave them unset — the zeus driver
    passes its own search box so retry_mode="uniform" works untouched."""
    strategy, eopts = phase2_setup(opts)
    if bounds is not None and eopts.retry_bounds is None:
        eopts = dataclasses.replace(
            eopts, retry_bounds=(float(bounds[0]), float(bounds[1])))
    return run_multistart(f, x0, strategy, eopts, pcount=pcount,
                          retry_key=retry_key, resume_from=resume_from)


def uniform_starts(key, n: int, dim: int, lower: float, upper: float, dtype):
    """use_pso=False fallback for both drivers: split the key so the starts
    are decorrelated from what a swarm init with the same key would draw;
    inf stands in for the absent PSO global best."""
    _, k_starts = jax.random.split(key)
    starts = jax.random.uniform(k_starts, (n, dim), dtype, lower, upper)
    return starts, jnp.asarray(jnp.inf, dtype)


def _select_best(res: BFGSResult) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Parallel reduction: best *converged* lane; fall back to best overall."""
    with jax.named_scope("zeus.finale"):
        fv = jnp.where(res.status == engine_mod.CONVERGED, res.fval, jnp.inf)
        any_conv = jnp.any(jnp.isfinite(fv))
        fv = jnp.where(any_conv, fv, res.fval)
        i = jnp.argmin(fv)
        return res.x[i], fv[i]


def zeus(
    f: Callable,
    key: jnp.ndarray,
    dim: int,
    lower: float,
    upper: float,
    opts: ZeusOptions = ZeusOptions(),
    resume: Optional[str] = None,  # checkpoint root to restore phase 2 from
) -> ZeusResult:
    """Single-host ZEUS (Alg. 7). jit-able end to end (checkpointing /
    fault preemption / `resume` excepted: those segment the phase-2 sweep
    loop on the host and must run un-jitted; the segments jit themselves).

    `resume` replays phase 1 (same key => bit-same swarm, cheap relative to
    phase 2) and restores the phase-2 carry from the newest COMMITted
    snapshot under `resume` — array-equal to the uninterrupted solve."""
    dtype = jnp.dtype(opts.dtype)
    # phase 1 by strategy name (PHASE1_STRATEGIES); use_pso=False skips it
    # entirely — no wasted objective evaluations
    starts, pso_best_f = run_phase1(f, key, dim, lower, upper, opts, dtype)
    res = solve_phase2(f, starts, opts,
                       retry_key=jax.random.fold_in(key, _RETRY_FOLD),
                       bounds=(lower, upper), resume_from=resume)
    best_x, best_f = _select_best(res)
    _warn_if_all_lanes_failed(res, starts.shape[0])
    return ZeusResult(
        best_x=best_x,
        best_f=best_f,
        raw=res,
        n_converged=res.n_converged,
        pso_best_f=pso_best_f,
        n_failed=res.n_failed,
        n_restarts=res.n_restarts,
    )


def _warn_if_all_lanes_failed(res: BFGSResult, n_lanes: int):
    """RuntimeWarning when the solve ends with EVERY lane failed — the
    caller would otherwise read a NaN/garbage best_x with no signal that
    the retry budget (if any) was exhausted on all of them. Host-side
    only: under jit the counters are tracers and the check is skipped."""
    nf = res.n_failed
    if nf is None or isinstance(nf, jax.core.Tracer):
        return
    if int(nf) >= n_lanes:
        import warnings

        budget = (int(jnp.max(res.n_restarts))
                  if res.n_restarts is not None else 0)
        warnings.warn(
            f"all {n_lanes} lanes ended failed (non-finite escape); "
            f"quarantine retries used per lane: up to {budget}. best_x is "
            "the least-bad failed iterate — consider retry_budget/"
            "retry_mode='uniform' or a different search box",
            RuntimeWarning, stacklevel=3)


def zeus_jit(f, dim, lower, upper, opts: ZeusOptions = ZeusOptions()):
    """Returns a jitted `key -> ZeusResult` closure (compile once, run many)."""
    return jax.jit(lambda key: zeus(f, key, dim, lower, upper, opts))


# ---------------------------------------------------------------------------
# Sequential ZEUS (Alg. 1) — the Fig. 2 baseline. Runs SerialBFGS lane by
# lane in python, stopping after required_c convergences, exactly like the
# paper's sequential loop (lines 9-20).
# ---------------------------------------------------------------------------
class SequentialZeusResult(NamedTuple):
    best_x: np.ndarray
    best_f: float
    n_converged: int
    n_started: int
    wall_time_s: float
    n_failed: int = 0  # lanes that ended with a non-finite fval


def sequential_zeus(
    f: Callable,
    key: jnp.ndarray,
    dim: int,
    lower: float,
    upper: float,
    opts: ZeusOptions = ZeusOptions(),
) -> SequentialZeusResult:
    if opts.phase1 != "pso":
        raise ValueError(
            "sequential_zeus is the paper's Alg. 1 baseline and only runs "
            "phase1='pso'; use zeus()/distributed_zeus for phase1="
            f"{opts.phase1!r}")
    t0 = time.perf_counter()
    if opts.use_pso and opts.pso.iter_pso > 0:
        swarm = sequential_pso(f, key, dim, lower, upper, opts.pso)
        starts = np.asarray(swarm.x)
    else:
        rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 2**31 - 1)))
        starts = rng.uniform(lower, upper, (opts.pso.n_particles, dim))

    required_c = opts.bfgs.required_c or len(starts)
    solve = jax.jit(functools.partial(serial_bfgs, f, opts=opts.bfgs))

    # The incumbent is seeded from the first evaluated lane so callers always
    # get an array back — even when every lane ends non-finite.
    best_x, best_f, c = None, np.inf, 0
    n_started, n_failed = 0, 0
    for x0 in starts:
        n_started += 1
        r = solve(jnp.asarray(x0, jnp.dtype(opts.dtype)))
        fv = float(r.fval)
        if not np.isfinite(fv):
            n_failed += 1
        # NaN compares false both ways, so a finite lane must explicitly
        # displace a non-finite incumbent
        better = (best_x is None or fv < best_f
                  or (np.isfinite(fv) and not np.isfinite(best_f)))
        if better:
            best_x, best_f = np.asarray(r.x), fv
        if int(r.status) == CONVERGED:
            c += 1
            if c >= required_c:
                break  # Alg. 1 line 17: stop early once enough runs converged
    return SequentialZeusResult(
        best_x=best_x,
        best_f=best_f,
        n_converged=c,
        n_started=n_started,
        wall_time_s=time.perf_counter() - t0,
        n_failed=n_failed,
    )
