"""Unified multistart quasi-Newton engine (paper Alg. 10, one copy).

The paper's phase 2 is "B independent quasi-Newton solves sharing a stop
protocol": sweep while  k < iter_max  AND  n_converged < required_c  AND any
lane active; lanes that converged/failed are frozen by masking — the TPU
analogue of CUDA warp lanes idling after `break`, with the atomicAdd
(converged)/stopFlag pair replaced by a replicated scalar count in the
lax.while_loop carry.

This module owns everything the driver shares across solvers:

  - lane init / active-lane masking / frozen-lane freezing,
  - Armijo/Wolfe line-search dispatch,
  - the curvature guard (skip the quasi-Newton update when δxᵀδg ≈ 0,
    DESIGN.md §8),
  - the required_c stop protocol, with the `pcount` hook through which the
    distributed driver plugs a cross-device psum (core/distributed.py),
  - status assignment (CONVERGED / DIVERGED / STOPPED),
  - chunked lane execution (below).

What *varies* between solvers — how the search direction is produced — is a
`DirectionStrategy`: `init_state / direction / update_state`. core/bfgs.py
implements it with a dense inverse Hessian (DenseBFGS), core/lbfgs.py with
the circular-buffer two-loop recursion (LBFGS). Strategies register in a
small solver registry so configuration can select them by name
(`ZeusOptions(solver="lbfgs")`).

Sweep execution modes
---------------------
`EngineOptions.sweep_mode` selects how a sweep is executed. "per_lane"
(default, seed behavior) vmaps the scalar `lane_step`. "batched" runs each
sweep as whole-(B, D)/(B, D, D) passes: the speculative batched Armijo
ladder (ONE objective launch for all K rungs of all lanes), one batched
value+grad (fused Pallas kernels for registered objective names), and one
fused guarded state update per sweep — the restructuring that makes the
kernels in kernels/ the actual hot path (DESIGN.md §10). The ladder probes
exactly the α sequence the sequential search does, so the accepted α is
identical whenever the evaluators round identically (exact for the vmap
fallback; fused-kernel objectives can flip a knife-edge accept by a ULP);
iterates agree to fp32 tolerance (tests/test_batched_sweep.py).
"megakernel" keeps the batched semantics but collapses the staged launches
into the fused VMEM-resident sweep kernel — 1 launch per sweep for the full
ladder, 2 for the adaptive ladder — with ARRAY-EQUAL results to "batched"
(kernels/sweep_megakernel.py, tests/test_megakernel.py); capability-gated
to analytic fused objectives + dense-H strategies, staged fallback with a
warning otherwise.

Chunked lane execution
----------------------
A monolithic `vmap` over B lanes materialises O(B·D²) of transient state per
sweep (dense-H temporaries, line-search trial batches) — the memory wall both
the ZEUS paper (§IV-C) and Zhou–Lange–Suchard (arXiv:1003.3272) identify for
batched second-order methods. With `lane_chunk=C` the engine runs each sweep
as `lax.map` over ceil(B/C) vmapped chunks: transient peak drops to O(C·D²)
while the stop counts stay sweep-synchronized across chunks (every chunk
advances one sweep, then the counts — and the `pcount` collective — see the
whole swarm). Chunked and monolithic runs therefore take the same sweeps
under the same stop protocol; per-lane numerics agree only up to XLA
fusion/reassociation differences (fp32 ULPs, amplifiable on chaotic
objectives), not bitwise.

Active-lane compaction
----------------------
Independent lanes converge at wildly different sweep counts, so the batched
path's tail keeps paying the full O(B·K) ladder for lanes that are already
frozen — the SIMT wasted-work tax Zhou–Lange–Suchard call out for batched
GPU optimizers. With `compact_every=n > 0` (batched mode only) the engine
gathers the still-active lanes into a dense prefix — a stable partition, so
active lanes keep their relative order — and runs the sweep only on that
prefix, scattering results back. Under jit the prefix length must be static,
so active counts are padded up to power-of-two *buckets* (`lax.switch` over
log2(B)+1 precompiled branch sizes, bounding jit cache growth); the
partition/bucket choice is refreshed every `compact_every` sweeps and stays
valid in between because frozen lanes never unfreeze. Tail objective work
drops from O(B·K) to O(bucket(active)·K) per sweep while trajectories stay
bit-identical to the uncompacted batched path: every evaluator on the path
is row-independent, so an active lane computes the same values at any batch
size, and frozen lanes inside the bucket padding are evaluated-but-masked
exactly as they would be uncompacted (lanes beyond the prefix are not
touched at all). Bit-identity additionally needs the evaluator's *codegen*
to be batch-size-stable — true of the hand-written batched evaluators
every named paper objective routes through (fused Pallas kernels and the
row-wise jnp references); vmap-of-scalar AD fallback closures can be
re-specialized by XLA with different FMA contraction per bucket size,
where the contract degrades to the chunked-execution one (same statuses,
fp32 iterates). See DESIGN.md §11 and tests/test_batched_sweep.py.

Global cross-chunk lane repacking
---------------------------------
Per-chunk compaction cuts each chunk's *rows* but the chunked sweep still
pays one lax.map trip per chunk: late in a solve, B/C sequential chunk
steps run even when every survivor would fit in one chunk. With
`repack_every=n > 0` (batched + lane_chunk only) the engine periodically
gathers ALL still-active lanes across chunks — a chunk-crossing gather of
the whole BatchLanes pytree, dense-H stack included — into the smallest
power-of-two number of full chunks, maps the sweep over those chunks only,
and scatters back: tail trips drop from B/C to bucket(ceil(active/C)),
surfaced as `BFGSResult.map_trips`. Every repacked chunk is exactly C wide,
so the evaluator batch size never varies and repacking alone is bit-exact
for *every* evaluator, vmap AD fallbacks included (the per-chunk-compaction
codegen caveat needs varying batch sizes to bite). Composes with
`compact_every` (prefix compaction inside each repacked chunk; plans are
recomputed against the repacked layout whenever the repack plan refreshes)
and with the distributed driver (each shard repacks its own lanes;
eval_rows/map_trips are psum'd). jit cache: (log2(B/C)+1) repack branches
× (log2(C)+1) compaction buckets step specializations worst case.

Adaptive speculative ladder
---------------------------
The full speculative ladder prices every sweep at K·B objective rows even
when most lanes accept rung 0 — the right trade early (one launch versus K
divergent round-trips) but pure overhead late. `ladder_len=L > 0` launches
only the first L rungs speculatively; lanes that exhaust them fall back to
masked sequential backtracking over the remaining rungs — unrolled
lax.cond probes, one (B,) launch per executed rung, skipped once every
lane has accepted. Every launch (short ladder, full ladder, each probe)
re-enters the same canonical trial graph with a host-constant α slice of
one shared cumprod ladder, which is what makes accepted α, exhaustion α,
and statuses bit-identical to the full ladder for identically-rounding
(launch-size-stable) evaluators — see core/linesearch.py for the codegen
reasoning and tests/test_batched_sweep.py::TestAdaptiveLadder for the
enforcement.

Auto-scheduling controller
--------------------------
All of the above are *static* schedules: the right repack/compact cadence
and ladder length depend on how the swarm actually converges (the paper's
§V trade-off study), which the user cannot know before the solve.
`schedule="auto"` (batched mode only) moves the choice into the while-loop
carry: a controller watches two schedule-invariant signals — the local
active-lane count and a running histogram of accepted Armijo rungs
(surfaced per lane by `armijo_backtracking_batch`) — and picks a *plan*
per refresh window of `schedule_every` sweeps. A plan is a point in a
small lattice: {static, dynamic} × candidate ladder lengths, where
"dynamic" is repack+compact (chunked) or prefix compaction (monolithic),
and the candidate ladders default to powers of two below ls_iters plus
the full ladder. The controller starts on the full-ladder static plan,
latches the dynamic plan once the active count drops below
`auto_active_frac`·B (latched = hysteresis by monotonicity: frozen lanes
never unfreeze), and re-targets the ladder at the smallest candidate
covering p90 of the window's accepted rungs — adopting shorter candidates
immediately (rows are monotone in ladder length, so shortening is free
insurance) and longer ones only after two consecutive windows map to the
same candidate (asymmetric hysteresis against thrash). Execution is a
lax.switch over the plan lattice whose branches re-enter the SAME
plan/execute closures the static schedules use, so every plan the
controller can pick is one of the already-bit-identical schedules and an
auto trajectory is array-equal to some static schedule sequence. That
argument is enforceable: `BFGSResult.schedule_trace` records the chosen
plan per window (a (n_windows, n_plans) count matrix, psum'd across
shards by the distributed driver), and `schedule="replay"` +
`schedule_plans=...` re-runs with a traced plan sequence forced — the
replay suite (tests/test_autoschedule.py) asserts array-equality.
Decisions are per shard and collective-free, like repacking: each shard
watches its own lanes, so shards in different convergence regimes pick
different plans without a psum. jit-cache bound: n_ladders ×
(1 + repack-bucket × compaction-bucket branches) step specializations
(DESIGN.md §13).
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
import warnings
from typing import Any, Callable, Dict, NamedTuple, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dual import grad_eval_cost, value_and_grad_fn
from repro.core.linesearch import (
    armijo_backtracking,
    armijo_backtracking_batch,
    wolfe_linesearch,
)

# status codes, matching the paper's result.status
DIVERGED = 0  # hit iter_max without |g| < theta (or NaN/Inf escape)
CONVERGED = 1
STOPPED = 2  # stop-flag: other lanes filled required_c first

_CURV_EPS = 1e-10

# sweep modes that run whole-batch sweeps (vs the vmapped per-lane step);
# "megakernel" is the batched semantics with the staged launches fused into
# the sweep megakernel, so every batched-only knob/schedule accepts both
_BATCHED_MODES = ("batched", "megakernel")


class BFGSResult(NamedTuple):
    """Result of one multistart solve (name kept from the seed API)."""

    x: jnp.ndarray  # (B, D) final iterates
    fval: jnp.ndarray  # (B,)
    grad_norm: jnp.ndarray  # (B,)
    status: jnp.ndarray  # (B,) int32 in {DIVERGED, CONVERGED, STOPPED}
    iterations: jnp.ndarray  # scalar — sweeps taken
    n_converged: jnp.ndarray  # scalar
    n_evals: Optional[jnp.ndarray] = None  # (B,) per-lane objective evals
    # scalar int32 — physical objective *rows* evaluated by the batched
    # sweep path (ladder trials + value_and_grad rows, padding included);
    # the tail-work metric active-lane compaction optimizes. Always 0 under
    # sweep_mode="per_lane", where rows are not instrumented. Diagnostic
    # only, and int32 because x64 is off in this codebase: wraps past ~2^31
    # rows (~100M lane-sweeps at ls_iters=20, or less when the distributed
    # driver psums per-device totals) — don't gate correctness on it at
    # pod scale.
    eval_rows: Optional[jnp.ndarray] = None
    # scalar int32 — chunk-step invocations the sweep driver issued (the
    # lax.map trip count): one per sweep monolithic, n_chunks per sweep
    # chunked-static, bucket(ceil(active/C)) per sweep under global lane
    # repacking (repack_every > 0) — the tail-latency metric repacking
    # optimizes. Psum'd across the mesh by the distributed driver.
    map_trips: Optional[jnp.ndarray] = None
    # (n_windows, n_plans) int32 — how many shards chose plan p in refresh
    # window w (schedule="auto"/"replay" only, else None). Single-host rows
    # are one-hot for executed windows and all-zero after an early stop;
    # decode with schedule_trace_plans() and replay with
    # EngineOptions(schedule="replay", schedule_plans=...). Psum'd across
    # the mesh by the distributed driver (per-shard decisions differ).
    schedule_trace: Optional[jnp.ndarray] = None
    # (B,) int32 — quarantine re-seeds consumed per lane (retry_budget > 0;
    # zeros otherwise). Lane-sharded (not psum'd) in the distributed
    # out_specs, like n_evals; sum it for the whole-mesh total.
    n_restarts: Optional[jnp.ndarray] = None
    # scalar int32 — lanes that ended failed (non-finite escape with any
    # retry budget exhausted). Psum'd across the mesh by the distributed
    # driver so callers can distinguish "converged" from "everything NaN'd".
    n_failed: Optional[jnp.ndarray] = None
    # launch.telemetry.TelemetryCarry — per-window host wall/rows/launch
    # deltas + the fitted c_row/c_launch cost estimates, recorded by the
    # cost-model hosted driver (auto_cost_model=True only, else None).
    # Like schedule_trace this documents what THIS run did; unlike it,
    # wall_s/energy_j are host measurements, not replayable quantities.
    telemetry: Optional[Any] = None


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """Solver-independent knobs of the multistart driver."""

    iter_max: int = 100
    theta: float = 1e-5  # gradient-norm convergence threshold Θ
    required_c: Optional[int] = None  # stop once this many lanes converged
    ls_iters: int = 20
    ls_c1: float = 0.3
    linesearch: str = "armijo"  # "armijo" (paper) | "wolfe" (beyond-paper)
    ad_mode: str = "forward"  # "forward" (paper) | "reverse" (beyond-paper)
    lane_chunk: Optional[int] = None  # None = one monolithic vmap
    # "per_lane": vmap over scalar lane_step (seed behavior).
    # "batched":  whole-(B, D)/(B, D, D) sweeps — speculative batched Armijo
    #             + fused batch kernels; armijo only. Same accepted α ladder
    #             and statuses as per_lane on fixed seeds (fp32-tolerance
    #             iterates); enforced by tests/test_batched_sweep.py.
    # "megakernel": batched semantics with the staged launches fused into
    #             ONE VMEM-resident Pallas sweep kernel (1–2 launches/sweep;
    #             kernels/sweep_megakernel.py). Array-equal to "batched"
    #             (tests/test_megakernel.py); requires an analytic fused
    #             objective + dense-H strategy within the VMEM cap, else
    #             falls back to the staged path with a RuntimeWarning.
    sweep_mode: str = "per_lane"
    # Active-lane compaction cadence (batched mode only). 0 disables; n > 0
    # refreshes the active-prefix partition and its power-of-two size bucket
    # every n sweeps, so a solve's tail does O(bucket(active)·K) objective
    # work instead of O(B·K). Bit-identical lanes either way (module
    # docstring); 1 is a good default when enabling — the per-sweep plan
    # cost is one argsort over lane flags, negligible next to the ladder.
    compact_every: int = 0
    # Global cross-chunk lane repacking cadence (batched + lane_chunk only).
    # 0 disables; n > 0 re-gathers all still-active lanes ACROSS chunks into
    # the smallest power-of-two number of full chunks every n sweeps, so the
    # tail's lax.map trip count drops from B/C to ceil(bucket(active)/C).
    # Composes with compact_every (per-chunk prefix compaction inside the
    # repacked chunks). Bit-identical lanes (module docstring).
    repack_every: int = 0
    # Adaptive speculative Armijo ladder (batched mode only). 0 runs the
    # full ls_iters-rung ladder in one launch (exact-parity default); L > 0
    # launches only the first L rungs speculatively and falls back to masked
    # sequential backtracking for lanes that exhaust them — same accepted α
    # by construction (core/linesearch.py), K·B → L·B + depth·B ladder rows
    # per sweep when most lanes accept early rungs.
    ladder_len: int = 0
    # Sweep schedule selection (batched mode only for "auto"/"replay").
    # "static": the repack_every/compact_every/ladder_len knobs above.
    # "auto":   the in-carry controller picks a (dynamic?, ladder) plan per
    #           refresh window from the active count + accepted-rung
    #           histogram (module docstring); the static knobs must stay 0.
    # "replay": force the plan sequence in schedule_plans (one plan index
    #           per window — record one from an auto run's schedule_trace
    #           via schedule_trace_plans()).
    schedule: str = "static"
    # Controller refresh window in sweeps: plans are re-decided (and the
    # gather plans re-computed) every schedule_every sweeps.
    schedule_every: int = 4
    # Replay-forced plan indices, one per window (schedule="replay" only).
    schedule_plans: Optional[Tuple[int, ...]] = None
    # Candidate ladder lengths for the auto controller (0 = the full
    # ls_iters ladder, always kept as the startup/most-conservative plan).
    # None derives {0} ∪ {powers of two < ls_iters}.
    auto_ladders: Optional[Tuple[int, ...]] = None
    # Enable the dynamic (repack+compact) plan once the LOCAL active count
    # drops below this fraction of the shard's lanes; latched once on.
    auto_active_frac: float = 0.5
    # ---- telemetry-aware cost model (DESIGN.md §17) ---------------------
    # schedule="auto" only: True moves the boundary plan decision to the
    # HOST — the solve runs the checkpoint driver's segmented loop with
    # segments clamped to schedule_every boundaries — and scores every
    # lattice candidate in measured seconds,
    #     score(L) = (L + E[fb])·active·c_row + E[fb]·c_launch,
    # with E[fb] from the window's rung-histogram tail mass
    # (linesearch.rung_tail_fallback_launches) and c_row/c_launch fitted
    # online (EMA over windows) from per-window wall clock
    # (launch/telemetry.py). Every executed plan is still a lattice
    # member decided at the same boundary, so schedule="replay" of the
    # recorded trace stays array-equal. Needs eager execution (host in
    # the loop — same constraint as checkpoint_every); incompatible with
    # lane_deadlines (a HostedSolve's segments are driven by the service,
    # which owns its own telemetry) and with the distributed program
    # driver.
    auto_cost_model: bool = False
    # (c_row, c_launch) constants fed to the cost model instead of the
    # EMA fit: decisions become a pure function of the carry — the
    # deterministic seam the exact-reproducibility tests pin
    # (tests/test_telemetry.py).
    telemetry_costs: Optional[Tuple[float, float]] = None
    # EMA smoothing weight of each new window's cost observation.
    telemetry_ema: float = 0.5
    # ---- fault tolerance (DESIGN.md §15) -------------------------------
    # Lane quarantine/retry: a lane that escapes to NaN/Inf (failed=True)
    # is re-seeded in-carry up to retry_budget times instead of freezing
    # forever (batched/megakernel sweeps only). retry_mode="perturb"
    # restarts from the lane's last finite iterate plus retry_sigma·N(0, I)
    # noise; "uniform" draws fresh from retry_bounds (required there, and
    # used as the sanitize-center for "perturb" when set — zeus() threads
    # its (lower, upper) automatically). Re-seeds consume a PRNG stream
    # carried in the loop state (seeded by run_multistart's retry_key), so
    # retries are deterministic and survive checkpoint resume exactly.
    retry_budget: int = 0
    retry_mode: str = "perturb"  # "perturb" | "uniform"
    retry_sigma: float = 0.1
    retry_bounds: Optional[Tuple[float, float]] = None
    # Sweep-carry checkpointing: > 0 snapshots the FULL while-loop carry
    # (lanes pytree incl. the dense-H stack, gather plans, controller
    # state, PRNG key data, row/trip counters) to checkpoint_dir every
    # checkpoint_every sweeps via checkpoint/manager.py's two-phase-commit
    # path. Requires eager execution (the driver runs jitted SEGMENTS of
    # checkpoint_every sweeps between host snapshots); resume via
    # run_multistart(resume_from=...) is array-equal to the uninterrupted
    # run. checkpoint_keep bounds the on-disk snapshot count (manager GC).
    checkpoint_every: int = 0
    checkpoint_dir: Optional[str] = None
    checkpoint_keep: int = 3
    # Deterministic fault-injection harness (debug/CI): a
    # launch.faults.FaultPlan whose NaN/kill events fire in-body keyed on
    # the carried sweep counter, and whose preempt_at_sweep makes the host
    # driver raise launch.faults.Preempted at that sweep boundary.
    fault_plan: Optional[Any] = None
    # ---- solve-service hooks (serve/service.py, DESIGN.md §16) ---------
    # Per-lane sweep deadlines: True adds a (B_flat,) int32 `deadline` to
    # the carry which the sweep prologue enforces — a lane whose nonzero
    # deadline is <= the sweep counter freezes as failed BEFORE stepping,
    # i.e. a lane admitted at sweep k0 with deadline k0+m runs exactly m
    # sweeps. This is how the solve service bounds each admitted request's
    # iteration budget inside a shared, indefinitely-running carry while
    # keeping per-lane trajectories array-equal to a solo solve (the solo
    # run's own iter_max stop and the deadline freeze produce the same
    # iterates and the same DIVERGED status). Deadlines are assigned by
    # HostedSolve.admit; 0 means none. Incompatible with retry_budget > 0
    # (a retry would resurrect an expired lane past its budget).
    lane_deadlines: bool = False


class DirectionStrategy(Protocol):
    """How a solver produces search directions. State is any pytree carried
    per lane (dense H for BFGS, (s, y, ρ) ring buffers for L-BFGS)."""

    def init_state(self, x0: jnp.ndarray) -> Any:
        """Per-lane direction state for a fresh start at x0."""
        ...

    def direction(self, state: Any, g: jnp.ndarray) -> jnp.ndarray:
        """Search direction p from the current state and gradient."""
        ...

    def update_state(self, state: Any, dx: jnp.ndarray, dg: jnp.ndarray) -> Any:
        """Absorb the secant pair (δx, δg). The engine only calls this with
        curvature-safe pairs and discards the result when the guard trips."""
        ...


class Lane(NamedTuple):
    """One optimization lane: shared fields + the strategy's state pytree."""

    x: jnp.ndarray
    f: jnp.ndarray
    g: jnp.ndarray
    converged: jnp.ndarray  # bool
    failed: jnp.ndarray  # bool (NaN/Inf escape)
    n_evals: jnp.ndarray  # int32 objective-eval counter (profiling)
    direction_state: Any


def lane_init(vg, strategy: DirectionStrategy, x0, theta,
              ad_mode: str = "forward") -> Lane:
    fval, g = vg(x0)
    gn = jnp.linalg.norm(g)
    return Lane(
        x=x0,
        f=fval,
        g=g,
        converged=gn < theta,
        failed=jnp.logical_not(jnp.isfinite(fval)),
        # eval cost of one gradient follows the configured AD mode (forward:
        # 1 + D passes, reverse: ~2) — not a hard-coded forward-mode count
        n_evals=jnp.asarray(grad_eval_cost(x0.shape[0], ad_mode), jnp.int32),
        direction_state=strategy.init_state(x0),
    )


def _guarded_update(strategy: DirectionStrategy, ds, dx, dg):
    """Skip the update on curvature breakdown (δxᵀδg ≈ 0) to avoid NaNs.

    The paper's CUDA kernel divides unguarded; any practical port needs this
    guard (DESIGN.md §8). Safe stand-in vectors keep 1/0 out of the update
    even on the discarded branch."""
    curv = jnp.dot(dx, dg)
    ok = jnp.logical_and(jnp.isfinite(curv), curv > _CURV_EPS)
    safe_dx = jnp.where(ok, dx, jnp.ones_like(dx))
    safe_dg = jnp.where(ok, dg, jnp.ones_like(dg))
    new = strategy.update_state(ds, safe_dx, safe_dg)
    return jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, ds)


def lane_step(f, vg, strategy: DirectionStrategy, opts: EngineOptions,
              lane: Lane) -> Lane:
    """One quasi-Newton step (Alg. 4 lines 10-16) with masking for frozen
    lanes: a converged/failed lane computes but keeps its old state."""
    x, fv, g = lane.x, lane.f, lane.g
    active = jnp.logical_not(jnp.logical_or(lane.converged, lane.failed))

    with jax.named_scope("zeus.phase2.update"):
        p = strategy.direction(lane.direction_state, g)
    with jax.named_scope("zeus.phase2.ladder"):
        # Safeguard: if p is not a descent direction (can happen after
        # numerical breakdown), restart from steepest descent — standard
        # practice.
        descent = jnp.dot(p, g) < 0
        p = jnp.where(descent, p, -g)

        if opts.linesearch == "armijo":
            ls = armijo_backtracking(
                f, x, p, fv, g, c1=opts.ls_c1, max_iters=opts.ls_iters
            )
        elif opts.linesearch == "wolfe":
            ls = wolfe_linesearch(f, x, p, fv, g, vg,
                                  max_iters=opts.ls_iters)
        else:
            raise ValueError(opts.linesearch)

        x_new = x + ls.alpha * p
    with jax.named_scope("zeus.phase2.gradient"):
        f_new, g_new = vg(x_new)
    with jax.named_scope("zeus.phase2.update"):
        ds_new = _guarded_update(strategy, lane.direction_state, x_new - x,
                                 g_new - g)

    with jax.named_scope("zeus.phase2.accept"):
        gn = jnp.linalg.norm(g_new)
        now_converged = gn < opts.theta
        now_failed = jnp.logical_not(
            jnp.logical_and(jnp.isfinite(f_new), jnp.all(jnp.isfinite(g_new)))
        )

        def keep(new, old):
            return jnp.where(active, new, old)

        return Lane(
            x=keep(x_new, x),
            f=keep(f_new, fv),
            g=keep(g_new, g),
            converged=jnp.where(active, now_converged, lane.converged),
            failed=jnp.where(active, now_failed, lane.failed),
            n_evals=lane.n_evals
            + jnp.where(
                active,
                ls.n_evals + grad_eval_cost(x.shape[0], opts.ad_mode), 0
            ).astype(jnp.int32),
            direction_state=jax.tree.map(keep, ds_new,
                                         lane.direction_state),
        )


# ---------------------------------------------------------------------------
# Batched sweep path (sweep_mode="batched").
#
# The per-lane path above vmaps a *scalar* step: the fused batch kernels in
# kernels/ are unreachable from it, and the per-lane Armijo while_loop makes
# every lane pay the slowest lane's backtracking depth as masked iterations.
# Here a sweep operates on whole (B, D) / (B, D, D) stacks: ONE speculative
# batched line search (the full α ladder in one objective launch), ONE
# batched value+grad, and ONE fused state update per sweep. The curvature
# guard and frozen-lane masking lift to batch level: lanes whose update is
# disabled pass ok=False and their state must come back unchanged.
# ---------------------------------------------------------------------------
class BatchedDirectionStrategy(Protocol):
    """Batch-level counterpart of DirectionStrategy. State is a pytree whose
    leaves carry a leading lane axis B."""

    def init_state_batch(self, X0: jnp.ndarray) -> Any:
        """Direction state stack for fresh starts X0 (B, D)."""
        ...

    def direction_batch(self, state: Any, G: jnp.ndarray) -> jnp.ndarray:
        """Directions P (B, D) from the state stack and gradients G."""
        ...

    def update_and_direction_batch(
        self, state: Any, dX: jnp.ndarray, dG: jnp.ndarray,
        ok: jnp.ndarray, G_new: jnp.ndarray,
    ) -> Tuple[Any, jnp.ndarray]:
        """Absorb the secant pairs and produce the *next* directions in one
        pass. `ok` (B,) bool disables the update per lane (curvature guard /
        frozen lanes): where False the returned state must equal the input
        state (and the pair may be garbage — implementations sanitize)."""
        ...


class VmappedStrategy:
    """Generic BatchedDirectionStrategy adapter: vmap the scalar strategy.

    Any registered solver gets the batched sweep's speculative line search
    and single-launch objective evaluations this way; the direction/update
    math stays per-lane vmapped. Strategies with a true batch-level kernel
    (DenseBFGS) advertise it via `as_batched()` instead."""

    def __init__(self, strategy: DirectionStrategy):
        self.strategy = strategy

    def init_state_batch(self, X0):
        return jax.vmap(self.strategy.init_state)(X0)

    def direction_batch(self, state, G):
        return jax.vmap(self.strategy.direction)(state, G)

    def update_and_direction_batch(self, state, dX, dG, ok, G_new):
        # safe stand-ins keep 1/0 and inf·0 out of the discarded branch,
        # mirroring _guarded_update's per-lane sanitisation
        safe_dX = jnp.where(ok[:, None], dX, jnp.ones_like(dX))
        safe_dG = jnp.where(ok[:, None], dG, jnp.ones_like(dG))
        new = jax.vmap(self.strategy.update_state)(state, safe_dX, safe_dG)

        def keep(n, o):
            return jnp.where(ok.reshape(ok.shape + (1,) * (n.ndim - 1)), n, o)

        state = jax.tree.map(keep, new, state)
        return state, self.direction_batch(state, G_new)


def as_batched_strategy(strategy: DirectionStrategy) -> BatchedDirectionStrategy:
    """Resolve the batch-level variant: the strategy's own (as_batched) when
    it has one, the generic vmapped adapter otherwise."""
    factory = getattr(strategy, "as_batched", None)
    if factory is not None:
        return factory()
    return VmappedStrategy(strategy)


class BatchLanes(NamedTuple):
    """Whole-swarm state for the batched sweep path. Unlike `Lane`, the
    next search direction P is carried across sweeps: fused update kernels
    emit (state', P') in one pass so state streams HBM once per sweep."""

    x: jnp.ndarray  # (B, D)
    f: jnp.ndarray  # (B,)
    g: jnp.ndarray  # (B, D)
    p: jnp.ndarray  # (B, D) next search direction
    converged: jnp.ndarray  # (B,) bool
    failed: jnp.ndarray  # (B,) bool
    n_evals: jnp.ndarray  # (B,) int32
    direction_state: Any  # batched pytree (leading lane axis)


def batch_lanes_init(bobj, bstrategy: BatchedDirectionStrategy,
                     X0: jnp.ndarray, theta) -> BatchLanes:
    F, G = bobj.value_and_grad_batch(X0)
    gn = jnp.linalg.norm(G, axis=-1)
    state = bstrategy.init_state_batch(X0)
    return BatchLanes(
        x=X0,
        f=F,
        g=G,
        p=bstrategy.direction_batch(state, G),
        converged=gn < theta,
        failed=jnp.logical_not(jnp.isfinite(F)),
        n_evals=jnp.full(X0.shape[:1], bobj.vg_cost(X0.shape[-1]), jnp.int32),
        direction_state=state,
    )


def batch_lanes_step(bobj, bstrategy: BatchedDirectionStrategy,
                     opts: EngineOptions, lanes: BatchLanes
                     ) -> Tuple[BatchLanes, jnp.ndarray, jnp.ndarray]:
    """One sweep over the whole stack (Alg. 4 lines 10-16, batch level).

    Returns (lanes', rows, rung_hist): rows is the scalar int32 count of
    physical objective rows this step evaluated — (ladder probes + 1
    value+grad) per lane in the stack, masked/padding lanes included — and
    rung_hist is the (ls_iters + 1,) int32 histogram of accepted Armijo
    rungs over the ACTIVE lanes in the stack (bin ls_iters = exhausted),
    the auto controller's ladder signal. The sweep driver sums rows into
    BFGSResult.eval_rows; deriving them here (from the actual stack size
    and the line search's actual probe count) is what keeps the accounting
    honest under compaction, repacking, and the adaptive ladder, whose
    per-sweep work is dynamic. The histogram counts active lanes only, so
    it is identical under every schedule (frozen/padding lanes are
    evaluated-but-masked and must not pollute the signal)."""
    X, F, G, P = lanes.x, lanes.f, lanes.g, lanes.p
    active = jnp.logical_not(jnp.logical_or(lanes.converged, lanes.failed))

    with jax.named_scope("zeus.phase2.ladder"):
        # descent safeguard, rowwise (same rule as the per-lane path)
        descent = jnp.sum(P * G, axis=-1) < 0
        P = jnp.where(descent[:, None], P, -G)

        ls = armijo_backtracking_batch(
            bobj.value_batch, X, P, F, G, c1=opts.ls_c1,
            max_iters=opts.ls_iters, ladder_len=opts.ladder_len,
        )
        X_new = X + ls.alpha[:, None] * P
    with jax.named_scope("zeus.phase2.gradient"):
        F_new, G_new = bobj.value_and_grad_batch(X_new)

    with jax.named_scope("zeus.phase2.accept"):
        dX, dG = X_new - X, G_new - G
        curv = jnp.sum(dX * dG, axis=-1)
        # curvature guard + frozen-lane freeze, lifted to batch level: a
        # single ok mask decides which lanes' state advances
        ok = jnp.logical_and(
            active, jnp.logical_and(jnp.isfinite(curv), curv > _CURV_EPS)
        )
    with jax.named_scope("zeus.phase2.update"):
        state, P_next = bstrategy.update_and_direction_batch(
            lanes.direction_state, dX, dG, ok, G_new
        )

    with jax.named_scope("zeus.phase2.accept"):
        gn = jnp.linalg.norm(G_new, axis=-1)
        now_converged = gn < opts.theta
        now_failed = jnp.logical_not(
            jnp.logical_and(
                jnp.isfinite(F_new), jnp.all(jnp.isfinite(G_new), axis=-1)
            )
        )

        def keep(new, old):
            mask = active.reshape(active.shape + (1,) * (new.ndim - 1))
            return jnp.where(mask, new, old)

        stepped = BatchLanes(
            x=keep(X_new, X),
            f=keep(F_new, F),
            g=keep(G_new, G),
            p=keep(P_next, lanes.p),
            converged=jnp.where(active, now_converged, lanes.converged),
            failed=jnp.where(active, now_failed, lanes.failed),
            n_evals=lanes.n_evals
            + jnp.where(
                active, ls.n_evals + bobj.vg_cost(X.shape[-1]), 0
            ).astype(jnp.int32),
            direction_state=state,
        )
        rows = (ls.n_evals.astype(jnp.int32) + 1) * X.shape[0]
        hist = jnp.zeros((opts.ls_iters + 1,), jnp.int32).at[ls.rung].add(
            active.astype(jnp.int32))
        return stepped, rows, hist


# ---------------------------------------------------------------------------
# Megakernel sweep path (sweep_mode="megakernel").
#
# Same sweep semantics as batch_lanes_step behind the same
# (lanes', rows, rung_hist) contract — every downstream schedule
# (lane_chunk, compaction, repacking, the auto controller) composes
# unchanged — but the four staged launches collapse into the fused Pallas
# sweep kernels (kernels/sweep_megakernel.py): ONE launch per sweep for the
# full speculative ladder, TWO (staged ladder + fused commit) for the
# adaptive ladder, whose sequential fallback deliberately stays un-fused
# (see the kernel module docstring). Exactness contract: trajectories,
# accepted α, statuses and counters are ARRAY-EQUAL to the staged batched
# path (tests/test_megakernel.py enforces it, no tolerance) — the kernel
# reproduces the staged program's reduction shapes and materialization
# seams rather than approximating them. Reached only for analytic
# fused-kernel objectives + dense-H strategies within the VMEM cap;
# run_multistart routes everything else back to batch_lanes_step with a
# warning (megakernel_unsupported_reason).
# ---------------------------------------------------------------------------
def megakernel_unsupported_reason(bobj, bstrategy, dim: int,
                                  opts: EngineOptions) -> Optional[str]:
    """Why sweep_mode='megakernel' cannot serve this solve, or None if it
    can. A non-None reason means run_multistart falls back to the staged
    batched path — bit-identical results, just staged launches."""
    from repro.core.objectives import analytic_fused_name
    from repro.kernels import ops as kernel_ops

    name = analytic_fused_name(bobj)
    if name is None:
        return (
            f"objective {getattr(bobj, 'name', None)!r} has no analytic "
            "fused kernel body to inline (custom-registered evaluators are "
            "opaque callables)")
    if not getattr(bstrategy, "megakernel_dense_h", False):
        return (
            f"direction strategy {type(bstrategy).__name__} does not "
            "advertise a dense-H megakernel form (megakernel_dense_h)")
    if opts.ls_iters < 1:
        return "ls_iters < 1 leaves no ladder to fuse"
    Dp = kernel_ops._padded_dim(dim)
    if name == "rosenbrock" and Dp != dim:
        return (
            f"rosenbrock at D={dim} needs lane padding to {Dp}, which is "
            "not exact for its coupled terms")
    if Dp > kernel_ops.MEGAKERNEL_MAX_DIM:
        return (
            f"padded dim {Dp} exceeds the {kernel_ops.MEGAKERNEL_MAX_DIM} "
            "VMEM cap for the resident (Dp, Dp) H tile")
    return None


def megakernel_lanes_step(bobj, bstrategy: BatchedDirectionStrategy,
                          opts: EngineOptions, lanes: BatchLanes
                          ) -> Tuple[BatchLanes, jnp.ndarray, jnp.ndarray]:
    """One fused sweep over the stack — batch_lanes_step's contract, 1–2
    launches. Only called when megakernel_unsupported_reason returned None;
    under REPRO_DISABLE_PALLAS=1 it delegates wholesale to the staged step,
    which IS the megakernel's reference semantics."""
    from repro.core.linesearch import armijo_thresholds, ladder_alphas
    from repro.kernels import ops as kernel_ops

    if not kernel_ops.pallas_enabled():
        return batch_lanes_step(bobj, bstrategy, opts, lanes)

    from repro.core.objectives import analytic_fused_name

    name = analytic_fused_name(bobj)
    X, F, G = lanes.x, lanes.f, lanes.g
    H = lanes.direction_state
    active = jnp.logical_not(jnp.logical_or(lanes.converged, lanes.failed))

    with jax.named_scope("zeus.phase2.fused_sweep"):
        # descent safeguard, rowwise — same rule, outside the kernel so the
        # ladder sees exactly the staged path's P
        descent = jnp.sum(lanes.p * G, axis=-1) < 0
        P = jnp.where(descent[:, None], lanes.p, -G)

        K = opts.ls_iters
        L = K if opts.ladder_len <= 0 else min(opts.ladder_len, K)
        if L == K:
            # full speculative ladder: ONE fused launch. The ladder constants
            # and the barriered Armijo thresholds are built by the same
            # linesearch helpers the staged program uses, so the kernel
            # compares the bit-identical rhs tensor.
            ddir = jnp.sum(G * P, axis=-1)
            alphas_np = ladder_alphas(K, X.dtype)
            rhs = armijo_thresholds(F, ddir, jnp.asarray(alphas_np),
                                    opts.ls_c1)
            X_new, F_new, G_new, state, P_next, _alpha, rung = (
                kernel_ops.sweep_megakernel_full(
                    name, X, P, G, H, active, rhs, alphas_np))
            ls_n_evals = jnp.asarray(K, jnp.int32)
        else:
            # adaptive ladder: the staged speculative launch + cond-guarded
            # fallback probes run VERBATIM (their early exit is the point —
            # see kernels/sweep_megakernel.py on why they stay un-fused), then
            # everything after the accept fuses into one commit launch.
            with jax.named_scope("zeus.phase2.ladder"):
                ls = armijo_backtracking_batch(
                    bobj.value_batch, X, P, F, G, c1=opts.ls_c1,
                    max_iters=K, ladder_len=opts.ladder_len,
                )
            X_new, F_new, G_new, state, P_next = (
                kernel_ops.sweep_megakernel_commit(
                    name, X, P, G, H, active, ls.alpha))
            ls_n_evals, rung = ls.n_evals, ls.rung

    # epilogue: textually in lockstep with batch_lanes_step (the reference
    # program) — convergence/failure flags, keep-masking, row accounting
    with jax.named_scope("zeus.phase2.accept"):
        gn = jnp.linalg.norm(G_new, axis=-1)
        now_converged = gn < opts.theta
        now_failed = jnp.logical_not(
            jnp.logical_and(
                jnp.isfinite(F_new), jnp.all(jnp.isfinite(G_new), axis=-1)
            )
        )

        def keep(new, old):
            mask = active.reshape(active.shape + (1,) * (new.ndim - 1))
            return jnp.where(mask, new, old)

        stepped = BatchLanes(
            x=keep(X_new, X),
            f=keep(F_new, F),
            g=keep(G_new, G),
            p=keep(P_next, lanes.p),
            converged=jnp.where(active, now_converged, lanes.converged),
            failed=jnp.where(active, now_failed, lanes.failed),
            n_evals=lanes.n_evals
            + jnp.where(
                active, ls_n_evals + bobj.vg_cost(X.shape[-1]), 0
            ).astype(jnp.int32),
            direction_state=state,
        )
        rows = (ls_n_evals.astype(jnp.int32) + 1) * X.shape[0]
        hist = jnp.zeros((opts.ls_iters + 1,), jnp.int32).at[rung].add(
            active.astype(jnp.int32))
        return stepped, rows, hist


# ---------------------------------------------------------------------------
# Active-lane compaction (sweep_mode="batched", compact_every > 0).
#
# Frozen lanes still occupy ladder rows in the batched sweep; once most of
# the swarm has converged the sweep is almost all masked work. Compaction
# stably partitions the lane axis (active first), then runs the sweep on a
# static-size prefix chosen from power-of-two buckets via lax.switch —
# dynamic shapes are impossible under jit, and bucketing bounds the compile
# cache at log2(B)+1 step specializations. The scatter back writes only the
# prefix rows; lanes beyond the prefix are untouched. Exact parity with the
# uncompacted path needs only row-independent batched evaluators (true of
# every fused kernel, the jnp references, and the vmap fallback): an active
# lane computes identical values at any batch size, and a frozen lane that
# lands in the bucket padding is evaluated-but-masked exactly as it would
# have been uncompacted.
# ---------------------------------------------------------------------------
def _active_mask(lanes) -> jnp.ndarray:
    return jnp.logical_not(jnp.logical_or(lanes.converged, lanes.failed))


def _compaction_buckets(n: int) -> Tuple[int, ...]:
    """Power-of-two prefix sizes up to n; the top bucket is always n itself
    (so a mostly-active swarm degrades to exactly the uncompacted sweep)."""
    sizes = []
    s = 1
    while s < n:
        sizes.append(s)
        s *= 2
    sizes.append(n)
    return tuple(sizes)


def _compaction_plan(active: jnp.ndarray, buckets: jnp.ndarray):
    """(perm, bucket_idx) for the current active set: a stable partition
    putting active lanes first (stable ⇒ active lanes keep their relative
    order, which keeps the gathered rows' values independent of *which*
    lanes froze) and the smallest bucket covering the active count."""
    perm = jnp.argsort(jnp.logical_not(active), stable=True).astype(jnp.int32)
    n_active = jnp.sum(active.astype(jnp.int32))
    bidx = jnp.searchsorted(buckets, n_active, side="left")
    return perm, jnp.minimum(bidx, buckets.shape[0] - 1).astype(jnp.int32)


def _compacted_sweep(step_fn, buckets: Tuple[int, ...], lanes,
                     perm: jnp.ndarray, bidx: jnp.ndarray):
    """One sweep on the active prefix only: gather rows perm[:bucket], step,
    scatter back. Valid as long as every active lane sits inside the prefix
    — guaranteed between plan refreshes because frozen lanes never unfreeze
    (converged/failed are sticky), so the active set only shrinks.

    `step_fn` returns (lanes', rows, rung_hist); the scatter passes both
    counters through, so the caller's eval_rows accounting sees the
    bucket's physical work and the controller sees the active lanes'
    accepted rungs (frozen lanes in the padding are masked out of the
    histogram by the step itself)."""

    def make_branch(size: int):
        def branch(operands):
            lanes, perm = operands
            idx = perm[:size]
            sub = jax.tree.map(lambda a: jnp.take(a, idx, axis=0), lanes)
            sub, rows, hist = step_fn(sub)
            return (
                jax.tree.map(lambda a, s: a.at[idx].set(s), lanes, sub),
                rows,
                hist,
            )

        return branch

    return jax.lax.switch(bidx, [make_branch(s) for s in buckets],
                          (lanes, perm))


# ---------------------------------------------------------------------------
# Global cross-chunk lane repacking (sweep_mode="batched", lane_chunk=C,
# repack_every > 0).
#
# Per-chunk compaction shrinks each chunk's *row* count but the sweep still
# pays one lax.map trip per chunk — B/C sequential chunk-steps even when the
# survivors of the whole swarm would fit in a single chunk. Repacking is the
# chunk-level analogue: every repack_every sweeps, gather ALL still-active
# lanes across chunks (a chunk-crossing gather of the full BatchLanes pytree,
# including the (B, D, D) dense-H stack) into the smallest power-of-two
# number of FULL chunks, run the sweep's lax.map over those chunks only, and
# scatter back. The trip count drops from B/C to bucket(ceil(active/C));
# every repacked chunk is exactly C wide, so the evaluator batch size never
# changes — which is why repacking is bit-exact even for evaluators whose
# codegen is only stable at a fixed batch size (the per-chunk compaction
# caveat does not apply to repacking alone). Composes with compact_every:
# the per-chunk active-prefix compaction then runs inside each repacked
# chunk, with its plans recomputed against the repacked layout.
# ---------------------------------------------------------------------------
def _repack_plan(active_flat: jnp.ndarray, chunk: int,
                 cbuckets: jnp.ndarray):
    """(gperm, gcidx) over the flattened lane axis: a stable partition
    putting active lanes first (stable ⇒ gathered row order is independent
    of *which* lanes froze) and the smallest chunk-count bucket covering
    ceil(active / chunk) full chunks."""
    gperm = jnp.argsort(jnp.logical_not(active_flat),
                        stable=True).astype(jnp.int32)
    n_active = jnp.sum(active_flat.astype(jnp.int32))
    n_needed = -(-n_active // chunk)  # ceil; 0 when nothing is active
    gcidx = jnp.searchsorted(cbuckets, n_needed, side="left")
    return gperm, jnp.minimum(gcidx, cbuckets.shape[0] - 1).astype(jnp.int32)


def _repacked_sweep(inner_sweep, cbuckets: Tuple[int, ...], chunk: int,
                    lanes, gperm: jnp.ndarray, gcidx: jnp.ndarray,
                    inner_aux):
    """One sweep on the repacked chunk set only.

    Gathers rows gperm[:m·C] of the flattened (n_chunks·C, ...) lanes into
    (m, C, ...) stacks, runs `inner_sweep` (a lax.map of the chunk step,
    optionally per-chunk-compacted via `inner_aux`) over the m chunks, and
    scatters back. Valid between plan refreshes for the same reason
    compaction is: frozen lanes never unfreeze, so every active lane stays
    inside the gathered prefix. Returns (lanes', rows, rung_hist)."""
    n_chunks = lanes.x.shape[0]

    def make_branch(m: int):
        def branch(operands):
            lanes, gperm, inner_aux = operands
            flat = jax.tree.map(
                lambda a: a.reshape((n_chunks * chunk,) + a.shape[2:]), lanes
            )
            idx = gperm[: m * chunk]
            sub = jax.tree.map(
                lambda a: jnp.take(a, idx, axis=0).reshape(
                    (m, chunk) + a.shape[1:]
                ),
                flat,
            )
            sub, rows, hist = inner_sweep(sub, inner_aux, m)
            flat = jax.tree.map(
                lambda a, s: a.at[idx].set(
                    s.reshape((m * chunk,) + s.shape[2:])
                ),
                flat, sub,
            )
            out = jax.tree.map(
                lambda a: a.reshape((n_chunks, chunk) + a.shape[1:]), flat
            )
            return out, rows, hist

        return branch

    return jax.lax.switch(gcidx, [make_branch(m) for m in cbuckets],
                          (lanes, gperm, inner_aux))


# ---------------------------------------------------------------------------
# Auto-scheduling controller (schedule="auto") and traced-plan replay
# (schedule="replay") — module docstring "Auto-scheduling controller".
#
# The controller lives in the while-loop carry and decides, at every
# schedule_every-sweep window boundary, which plan of a small host-defined
# lattice the next window runs: {static, dynamic} × candidate ladder
# lengths. Every plan re-enters the SAME plan/execute closures the static
# schedules use (lax.switch over the lattice), so an auto trajectory is by
# construction array-equal to the static schedule sequence its
# schedule_trace records — the parity argument schedule="replay" turns into
# a test.
# ---------------------------------------------------------------------------
class _AutoState(NamedTuple):
    """Controller carry: current plan, latched dynamic flag, the previous
    window's ladder candidate (the asymmetric hysteresis consults it when
    lengthening the ladder), the accepted-rung histogram accumulated over
    the current window, and the per-window plan trace."""

    plan: jnp.ndarray  # scalar int32 — current plan index
    dyn_on: jnp.ndarray  # scalar bool — dynamic plan latched
    prev_lidx: jnp.ndarray  # scalar int32 — last window's ladder candidate
    hist: jnp.ndarray  # (ls_iters + 1,) int32 — current window's rungs
    trace: jnp.ndarray  # (n_windows, n_plans) int32


def _auto_ladders(opts: EngineOptions) -> Tuple[int, ...]:
    """Canonical candidate ladder lengths for the controller: sorted by
    effective length (0 = the full ls_iters ladder) with the full ladder
    LAST — index n_ladders-1 is the startup / most conservative plan."""
    K = opts.ls_iters
    if opts.auto_ladders is not None:
        cand = {int(L) for L in opts.auto_ladders}
        for L in cand:
            if L < 0 or L > K:
                raise ValueError(
                    f"auto_ladders entries must be in [0, ls_iters={K}] "
                    f"(got {L})")
    else:
        cand = {0}
        L = 1
        while L < K:
            cand.add(L)
            L *= 2
    cand.discard(K)  # ladder_len == K is the full ladder; canonical spelling
    cand.add(0)
    return tuple(sorted(cand - {0})) + (0,)


def auto_plan_lattice(opts: EngineOptions) -> Tuple[Tuple[int, int], ...]:
    """The (dynamic, ladder_len) plans schedule="auto" can pick, in
    plan-index order (index p = dynamic · n_ladders + ladder_idx).
    dynamic=1 means repack+compact (chunked) / prefix compaction
    (monolithic). Decode ScheduleTrace rows against this."""
    ladders = _auto_ladders(opts)
    return tuple((dyn, L) for dyn in (0, 1) for L in ladders)


def schedule_trace_plans(trace) -> Tuple[int, ...]:
    """Decode a single-shard ScheduleTrace into per-window plan indices,
    suitable for EngineOptions(schedule="replay", schedule_plans=...).
    All-zero rows (windows after an early stop) decode to plan 0 — those
    windows are never executed by the replay either."""
    t = np.asarray(trace)
    return tuple(int(np.argmax(row)) if row.any() else 0 for row in t)


class EngineCarry(NamedTuple):
    """The sweep driver's full while-loop carry — ONE pytree holding every
    bit of solve state, so a snapshot of it IS the solve (DESIGN.md §15).

    Checkpoint/resume round-trips this structure through
    checkpoint/manager.py; array-equal resume requires that nothing the
    sweeps read lives outside it — which is why the retry PRNG stream is
    carried as raw uint32 key data (np-serializable, unlike typed keys) and
    the row/trip counters accumulate in-carry rather than post-hoc."""

    k: jnp.ndarray  # scalar int32 — sweeps completed
    lanes: Any  # BatchLanes / Lane stack (chunked: leading (n_chunks, C))
    n_conv: jnp.ndarray  # scalar int32 — global converged count (pcount'd)
    n_act: jnp.ndarray  # scalar int32 — global active count (pcount'd)
    aux: Any  # gather plans: () | (perm, bidx) | (gperm, gcidx[, cperm, cbidx])
    rows: jnp.ndarray  # scalar int32 — physical objective rows so far
    trips: jnp.ndarray  # scalar int32 — chunk-step trips so far
    astate: Any  # _AutoState (schedule="auto"/"replay") or ()
    rkey: jnp.ndarray  # raw uint32 PRNG key data for quarantine re-seeds
    n_restarts: jnp.ndarray  # (B_flat,) int32 — re-seeds consumed per lane
    replan: jnp.ndarray  # scalar bool — force a gather-plan refresh next sweep
    deadline: jnp.ndarray  # (B_flat,) int32 — per-lane sweep deadline (0=none)
    telem: Any  # launch.telemetry.TelemetryCarry (auto_cost_model) or ()


class MultistartProgram(NamedTuple):
    """run_multistart's solve, factored as (init, cond, body, finalize) over
    an EngineCarry — the building blocks the segmented checkpoint driver and
    the distributed fault-tolerant driver re-assemble around host control.
    `body` advances exactly one sweep; `cond` is the stop protocol."""

    make_carry0: Callable[[], "EngineCarry"]
    cond: Callable[["EngineCarry"], jnp.ndarray]
    body: Callable[["EngineCarry"], "EngineCarry"]
    finalize: Callable[["EngineCarry"], BFGSResult]
    opts: EngineOptions
    required_c: int


@dataclasses.dataclass
class HostedSolve:
    """A multistart solve held OPEN under host control (DESIGN.md §16).

    Where `run_multistart` drives a carry from init to finalize itself,
    a HostedSolve hands the segmented loop's jitted pieces to the caller:
    `segment()` advances the sweep while-loop to the next host boundary,
    `lane_view()` reads per-slot results there (the harvest), `admit()`
    seeds fresh lanes into chosen slots mid-flight, and `empty_carry()`
    starts a pool with every slot vacant. This is the engine half of the
    continuous-batching solve service (serve/service.py): lanes are slots
    of a persistent pool, requests are admitted into freed slots at
    segment boundaries, and per-lane trajectories stay array-equal to a
    solo solve because admission touches nothing outside the admitted
    rows. All callables are jitted and shared through the hosted jit
    cache, so opening the same solve signature twice compiles once."""

    _carry0: Callable  # (X, rkey_data) -> EngineCarry
    _seg: Callable  # (carry, k_end) -> carry advanced to a boundary
    _fin: Callable  # carry -> BFGSResult
    _cond: Callable  # carry -> bool: any sweep work left?
    _admit: Callable  # (carry, mask, X, deadlines) -> carry
    _vacate: Callable  # carry -> carry with every slot frozen vacant
    _view: Callable  # carry -> flat per-slot harvest dict
    opts: EngineOptions
    B: int  # admittable slots (flat indices >= B are chunk padding)
    B_flat: int  # flat lane axis incl. padding (mask/deadline length)
    dim: int
    required_c: int
    _x0: jnp.ndarray  # (B, dim) placeholder starts for empty_carry
    _rkey0: jnp.ndarray

    def init_carry(self, X0=None, retry_key=None) -> "EngineCarry":
        rk = self._rkey0
        if retry_key is not None:
            rk = (jax.random.key_data(retry_key)
                  if jnp.issubdtype(jnp.asarray(retry_key).dtype,
                                    jax.dtypes.prng_key)
                  else jnp.asarray(retry_key, jnp.uint32))
        X0 = self._x0 if X0 is None else jnp.asarray(X0)
        return self._carry0(X0, rk)

    def empty_carry(self, retry_key=None) -> "EngineCarry":
        """A pool with every slot vacant (frozen, harvestable-as-nothing);
        the service's starting state."""
        return self._vacate(self.init_carry(retry_key=retry_key))

    def segment(self, carry, k_end) -> "EngineCarry":
        """Advance the sweep loop until k reaches k_end, every lane is
        frozen, or required_c lanes converged — whichever comes first."""
        return self._seg(carry, jnp.asarray(k_end, jnp.int32))

    def running(self, carry) -> bool:
        return bool(self._cond(carry))

    def admit(self, carry, mask, X, deadlines) -> "EngineCarry":
        """Seed fresh lanes into the mask'd flat slots of a live carry.
        X is (B, dim) start points (only mask'd rows are read); deadlines
        is (B_flat,) int32 absolute sweep deadlines (0 = none)."""
        return self._admit(carry, jnp.asarray(mask), jnp.asarray(X),
                           jnp.asarray(deadlines, jnp.int32))

    def lane_view(self, carry) -> dict:
        """Host copy of the flat per-slot harvest view: k, x, f,
        grad_norm, converged, failed, n_evals, deadline (np arrays)."""
        return {k: np.asarray(v)
                for k, v in jax.device_get(self._view(carry)).items()}

    def finalize(self, carry) -> BFGSResult:
        return self._fin(carry)


# hosted-driver jit cache (see run_multistart's segmented section): maps a
# solve signature to its (init, segment, finalize, cond, admit, vacate,
# view) jits so repeated checkpointed solves — and every HostedSolve the
# service opens for the same signature — pay tracing/compilation once,
# like a user-jitted un-checkpointed solve does
_HOSTED_JIT_CACHE: Dict[Any, Tuple[Callable, ...]] = {}


def _hashable(obj):
    """obj if it can key a dict, else its identity (same semantics as
    jax.jit's function-identity caching: a fresh lambda misses)."""
    try:
        hash(obj)
        return obj
    except TypeError:
        return id(obj)


def _freeze_config(strategy) -> Tuple:
    """Hashable snapshot of a strategy's instance config (e.g. LBFGS
    memory). Non-primitive values degrade to identity, so exotic stateful
    strategies safely miss the cache rather than alias each other."""
    cfg = getattr(strategy, "__dict__", None) or {}
    return tuple(
        (k, v if isinstance(v, (int, float, str, bool, type(None)))
         else id(v))
        for k, v in sorted(cfg.items()))


def _in_phase2(fn: Callable) -> Callable:
    """`fn` traced under the `zeus.phase2` name scope, whichever loop
    calls it: the in-graph loop, a shard_map program, a jitted host
    segment. A fresh scope per call: one `jax.named_scope` object used as
    a decorator keeps a single saved context, which nested traces of the
    same function would restore wrongly."""

    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope("zeus.phase2"):
            return fn(*args, **kwargs)

    return scoped


def run_multistart(
    f: Callable,
    x0: jnp.ndarray,  # (B, D) starting points (the post-PSO swarm)
    strategy: DirectionStrategy,
    opts: EngineOptions = EngineOptions(),
    pcount: Optional[Callable] = None,  # cross-device converged-count reducer
    retry_key: Optional[jnp.ndarray] = None,  # PRNG key for quarantine re-seeds
    resume_from: Optional[str] = None,  # checkpoint root to restore from
    _as_program: bool = False,  # return the MultistartProgram instead
    _as_host: bool = False,  # return a HostedSolve (open_multistart)
) -> BFGSResult:
    """Run B independent quasi-Newton solves until required_c converge.

    `pcount` lets the distributed driver plug a psum across the mesh so the
    stop flag is global (see core/distributed.py); default is local sum.
    With `opts.lane_chunk=C` the B lanes run as lax.map over ceil(B/C)
    chunks (padded with frozen lanes when C ∤ B) — same sweeps, same stop
    protocol, O(C·D²) transient memory. With `opts.sweep_mode="batched"`
    each sweep (or chunk thereof) runs as whole-batch passes: speculative
    batched Armijo + fused batch kernels instead of a vmapped scalar step;
    `opts.compact_every=n > 0` additionally compacts each sweep (or chunk)
    onto its active-lane prefix — bit-identical lanes, O(bucket(active)·K)
    tail work; `opts.repack_every=n > 0` (chunked batched only) globally
    repacks the surviving lanes into fewer full chunks so the tail's
    lax.map trip count tracks the active set too; `opts.ladder_len=L > 0`
    shortens the speculative Armijo ladder with a masked sequential
    fallback (module docstring for all three).
    """
    B, D = x0.shape
    required_c = opts.required_c if opts.required_c is not None else B

    def count(c):
        """The stop protocol's global count: a psum across the mesh under
        distributed_zeus, the local count otherwise."""
        if pcount is None:
            return c
        with jax.named_scope("zeus.phase2.stop"):
            return pcount(c)

    if opts.compact_every < 0:
        raise ValueError(f"compact_every must be >= 0 (got {opts.compact_every})")
    if opts.compact_every > 0 and opts.sweep_mode not in _BATCHED_MODES:
        raise ValueError(
            "compact_every > 0 requires sweep_mode='batched'/'megakernel' "
            f"(got sweep_mode={opts.sweep_mode!r})"
        )
    if opts.repack_every < 0:
        raise ValueError(f"repack_every must be >= 0 (got {opts.repack_every})")
    if opts.repack_every > 0 and opts.sweep_mode not in _BATCHED_MODES:
        raise ValueError(
            "repack_every > 0 requires sweep_mode='batched'/'megakernel' "
            f"(got sweep_mode={opts.sweep_mode!r})"
        )
    if opts.repack_every > 0 and opts.lane_chunk is None:
        raise ValueError(
            "repack_every > 0 repacks lanes ACROSS chunks and needs "
            "lane_chunk set (got lane_chunk=None)"
        )
    if opts.ladder_len < 0:
        raise ValueError(f"ladder_len must be >= 0 (got {opts.ladder_len})")
    if opts.ladder_len > 0 and opts.sweep_mode not in _BATCHED_MODES:
        raise ValueError(
            "ladder_len > 0 shortens the speculative batched ladder and "
            "requires sweep_mode='batched'/'megakernel' "
            f"(got {opts.sweep_mode!r}); the per-lane sequential search is "
            "already adaptive"
        )
    if opts.schedule not in ("static", "auto", "replay"):
        raise ValueError(
            f"unknown schedule {opts.schedule!r}; "
            "expected 'static', 'auto' or 'replay'"
        )
    scheduling = opts.schedule != "static"
    if scheduling:
        if opts.sweep_mode not in _BATCHED_MODES:
            raise ValueError(
                f"schedule={opts.schedule!r} drives the batched sweep's "
                f"plans and requires sweep_mode='batched'/'megakernel' "
                f"(got {opts.sweep_mode!r})"
            )
        if opts.compact_every or opts.repack_every or opts.ladder_len:
            raise ValueError(
                f"schedule={opts.schedule!r} owns the cadence/ladder plan; "
                "leave repack_every/compact_every/ladder_len at 0 (got "
                f"repack_every={opts.repack_every}, "
                f"compact_every={opts.compact_every}, "
                f"ladder_len={opts.ladder_len})"
            )
        if opts.schedule_every <= 0:
            raise ValueError(
                f"schedule_every must be >= 1 (got {opts.schedule_every})")

    # --- telemetry cost-model validation (DESIGN.md §17) -----------------
    cost_model = opts.auto_cost_model
    if cost_model and opts.schedule != "auto":
        raise ValueError(
            "auto_cost_model=True re-scores the schedule='auto' plan "
            f"lattice and requires schedule='auto' (got {opts.schedule!r})")
    if opts.telemetry_costs is not None:
        if not cost_model:
            raise ValueError(
                "telemetry_costs feeds the cost model fixed (c_row, "
                "c_launch) constants and requires auto_cost_model=True")
        if len(opts.telemetry_costs) != 2:
            raise ValueError(
                "telemetry_costs must be (c_row, c_launch) "
                f"(got {opts.telemetry_costs!r})")
    if cost_model and opts.lane_deadlines:
        raise ValueError(
            "auto_cost_model=True drives its own host-segmented loop and "
            "is incompatible with lane_deadlines=True (the solve service "
            "drives segments itself; it records pool telemetry instead)")
    if cost_model and (_as_program or _as_host):
        raise ValueError(
            "auto_cost_model=True needs the host in the sweep loop (the "
            "boundary plan decision reads measured window costs) and is "
            "unavailable through the program/hosted-pool drivers "
            "(distributed_zeus, open_multistart)")

    # --- fault-tolerance option validation (DESIGN.md §15) ---------------
    from repro.launch.faults import (  # import-cycle-safe (launch is leaf)
        Preempted,
        injection_masks as faults_masks,
        reseed_lost_lanes as faults_reseed,
    )

    if opts.retry_budget < 0:
        raise ValueError(
            f"retry_budget must be >= 0 (got {opts.retry_budget})")
    retrying = opts.retry_budget > 0
    if retrying and opts.sweep_mode not in _BATCHED_MODES:
        raise ValueError(
            "retry_budget > 0 re-seeds lanes through the batched init/eval "
            "stack and requires sweep_mode='batched'/'megakernel' "
            f"(got {opts.sweep_mode!r})")
    if opts.retry_mode not in ("perturb", "uniform"):
        raise ValueError(
            f"unknown retry_mode {opts.retry_mode!r}; "
            "expected 'perturb' or 'uniform'")
    if retrying and opts.retry_mode == "uniform" and opts.retry_bounds is None:
        raise ValueError(
            "retry_mode='uniform' draws fresh points uniformly and needs "
            "retry_bounds=(lower, upper)")
    deadlining = opts.lane_deadlines
    if deadlining and retrying:
        raise ValueError(
            "lane_deadlines=True is incompatible with retry_budget > 0: a "
            "quarantine retry would resurrect a deadline-expired lane past "
            "its per-request budget")
    if opts.checkpoint_every < 0:
        raise ValueError(
            f"checkpoint_every must be >= 0 (got {opts.checkpoint_every})")
    checkpointing = opts.checkpoint_every > 0
    if checkpointing and not opts.checkpoint_dir:
        raise ValueError(
            "checkpoint_every > 0 needs checkpoint_dir to write snapshots to")
    fault_plan = opts.fault_plan
    injecting = fault_plan is not None and fault_plan.has_injections
    preempt_at = None if fault_plan is None else fault_plan.preempt_at_sweep
    # checkpointing / preemption / resume need the HOST in the sweep loop
    # (segmented lax.while_loop with np snapshots in between) — impossible
    # under an enclosing jit trace, so fail loudly instead of miscompiling
    hosted = (checkpointing or resume_from is not None
              or preempt_at is not None or cost_model) and not _as_program
    if (hosted or _as_host) and isinstance(x0, jax.core.Tracer):
        raise ValueError(
            "checkpoint_every/fault_plan.preempt_at_sweep/resume_from/"
            "auto_cost_model drive a host-segmented sweep loop and cannot "
            "run under an enclosing jit trace; call run_multistart "
            "un-jitted (it jits its own segments)")

    if opts.sweep_mode in _BATCHED_MODES:
        if opts.linesearch != "armijo":
            raise ValueError(
                f"sweep_mode={opts.sweep_mode!r} supports linesearch="
                f"'armijo' only (got {opts.linesearch!r}); use "
                "sweep_mode='per_lane'"
            )
        from repro.core.objectives import as_batched  # import-cycle-safe

        bobj = as_batched(f, ad_mode=opts.ad_mode)
        bstrategy = as_batched_strategy(strategy)
        step_impl = batch_lanes_step
        if opts.sweep_mode == "megakernel":
            reason = megakernel_unsupported_reason(bobj, bstrategy, D, opts)
            if reason is None:
                step_impl = megakernel_lanes_step
            else:
                from repro.kernels import ops as kernel_ops

                warnings.warn(
                    f"sweep_mode='megakernel' (D={D}, padded Dp="
                    f"{kernel_ops._padded_dim(D)}, cap "
                    f"{kernel_ops.MEGAKERNEL_MAX_DIM}): {reason}; running "
                    "the staged batched path instead (bit-identical results)",
                    RuntimeWarning, stacklevel=2,
                )
        init_chunk = lambda X: batch_lanes_init(bobj, bstrategy, X, opts.theta)
        step_chunk = functools.partial(step_impl, bobj, bstrategy, opts)
    elif opts.sweep_mode == "per_lane":
        vg = value_and_grad_fn(f, opts.ad_mode)
        init_one = lambda x: lane_init(vg, strategy, x, opts.theta,
                                       opts.ad_mode)
        step_one = functools.partial(lane_step, f, vg, strategy, opts)
        init_chunk = jax.vmap(init_one)
        step_vmapped = jax.vmap(step_one)
        # same (lanes', rows, rung_hist) contract as the batched step so the
        # sweep driver below is schedule-agnostic; per_lane rows/rungs are
        # not instrumented (eval_rows stays 0, the histogram empty)
        step_chunk = lambda ls: (step_vmapped(ls), jnp.zeros((), jnp.int32),
                                 jnp.zeros((opts.ls_iters + 1,), jnp.int32))
    else:
        raise ValueError(
            f"unknown sweep_mode {opts.sweep_mode!r}; "
            "expected 'per_lane', 'batched' or 'megakernel'"
        )

    C = opts.lane_chunk
    chunked = C is not None and 0 < C < B
    batched = opts.sweep_mode in _BATCHED_MODES
    if chunked:
        n_chunks = -(-B // C)
        pad = n_chunks * C - B
        B_flat = n_chunks * C

        def init_lanes(X=None):
            X = x0 if X is None else X
            if pad:
                X = jnp.concatenate([X, jnp.broadcast_to(X[:1], (pad, D))])
            lanes = jax.lax.map(init_chunk, X.reshape(n_chunks, C, D))
            if pad:
                # padding lanes are frozen-from-birth: never active, never
                # counted, never retried
                is_pad = (jnp.arange(B_flat) >= B).reshape(n_chunks, C)
                lanes = lanes._replace(
                    converged=jnp.logical_and(lanes.converged,
                                              jnp.logical_not(is_pad)),
                    failed=jnp.logical_or(lanes.failed, is_pad),
                )
            return lanes

        def sweep(ls):
            new, rows, hist = jax.lax.map(step_chunk, ls)
            return new, jnp.sum(rows), jnp.sum(hist, axis=0)

        group, n_groups = C, n_chunks
    else:
        B_flat = B
        init_lanes = lambda X=None: init_chunk(x0 if X is None else X)
        sweep = step_chunk
        group, n_groups = B, 1
    # flat-lane padding mask (all-False when unchunked/unpadded): the retry
    # and injection passes address lanes on this flattened axis
    is_pad_flat = jnp.arange(B_flat) >= B

    # physical objective-row accounting (batched path only): the step
    # functions report their own rows ((probes + 1) per lane actually
    # stacked), so eval_rows stays honest under compaction, repacking, and
    # the adaptive ladder; init evaluates one value+grad row per lane
    eval_rows0 = jnp.asarray(n_groups * group if batched else 0, jnp.int32)
    trips_static = jnp.asarray(n_groups, jnp.int32)  # chunk-steps per sweep

    compacting = batched and opts.compact_every > 0
    # repacking needs 2+ chunks to rebalance across; a single-chunk run
    # (lane_chunk >= B) degenerates to the static schedule silently
    repacking = batched and opts.repack_every > 0 and chunked

    if compacting:
        buckets = _compaction_buckets(group)
        buckets_arr = jnp.asarray(buckets, jnp.int32)
        plan_one = functools.partial(_compaction_plan, buckets=buckets_arr)

    if repacking:
        cbuckets = _compaction_buckets(n_chunks)  # chunk-COUNT buckets
        cbuckets_arr = jnp.asarray(cbuckets, jnp.int32)
        gplan = functools.partial(_repack_plan, chunk=C,
                                  cbuckets=cbuckets_arr)
        if compacting:
            cplan_fn = jax.vmap(plan_one)

            def fresh_inner_aux(lanes, gperm):
                # per-chunk compaction plans of the REPACKED layout: gather
                # the active flags the way the sweep will gather the lanes
                act = _active_mask(lanes).reshape(-1)
                gact = jnp.take(act, gperm).reshape(n_chunks, C)
                return cplan_fn(gact)

            def inner_sweep(sub, inner_aux, m):
                cperm, cbidx = inner_aux
                new, rows, hist = jax.lax.map(
                    lambda args: _compacted_sweep(step_chunk, buckets, *args),
                    (sub, cperm[:m], cbidx[:m]),
                )
                return new, jnp.sum(rows), jnp.sum(hist, axis=0)
        else:
            def inner_sweep(sub, inner_aux, m):
                new, rows, hist = jax.lax.map(step_chunk, sub)
                return new, jnp.sum(rows), jnp.sum(hist, axis=0)

        def refresh_plans(k, lanes, aux, force=False):
            """Boundary-sweep plan refreshes, both skipped via lax.cond in
            between (the stored plans stay valid: frozen lanes never
            unfreeze, so the active set only shrinks). The per-chunk
            compaction plans are relative to the repacked layout, so a
            repack refresh forces a compaction re-plan too. `force` (a
            quarantine re-admission or an elastic restore) breaks the
            only-shrinks invariant and refreshes everything off-boundary."""
            renew_g = jnp.logical_or((k % opts.repack_every) == 0, force)
            gperm, gcidx = jax.lax.cond(
                renew_g,
                lambda ls, a: gplan(_active_mask(ls).reshape(-1)),
                lambda ls, a: a[:2],
                lanes, aux,
            )
            if not compacting:
                return (gperm, gcidx)
            renew_c = jnp.logical_or(renew_g,
                                     (k % opts.compact_every) == 0)
            cperm, cbidx = jax.lax.cond(
                renew_c,
                lambda ls, gp, a: fresh_inner_aux(ls, gp),
                lambda ls, gp, a: a[2:],
                lanes, gperm, aux,
            )
            return (gperm, gcidx, cperm, cbidx)

        def repacked(lanes, aux):
            gperm, gcidx = aux[0], aux[1]
            inner_aux = aux[2:]
            lanes, srows, _ = _repacked_sweep(inner_sweep, cbuckets, C, lanes,
                                              gperm, gcidx, inner_aux)
            return lanes, srows, cbuckets_arr[gcidx]

        def make_aux0(ls):
            gp0 = gplan(_active_mask(ls).reshape(-1))
            return gp0 + fresh_inner_aux(ls, gp0[0]) if compacting else gp0
    elif compacting:
        if chunked:
            plan_fn = jax.vmap(plan_one)  # each chunk compacts independently

            def compacted(lanes, perm, bidx):
                new, rows, _ = jax.lax.map(
                    lambda args: _compacted_sweep(step_chunk, buckets, *args),
                    (lanes, perm, bidx),
                )
                return new, jnp.sum(rows)
        else:
            plan_fn = plan_one

            def compacted(lanes, perm, bidx):
                new, rows, _ = _compacted_sweep(step_chunk, buckets, lanes,
                                                perm, bidx)
                return new, rows

        make_aux0 = lambda ls: plan_fn(_active_mask(ls))
    else:
        make_aux0 = lambda ls: ()

    # ------------------------------------------------------------------
    # Auto-scheduling controller (schedule="auto") / traced-plan replay
    # (schedule="replay"). Every plan executor re-enters the same step and
    # gather/scatter machinery the static schedules use, parameterized only
    # by the plan's ladder length — which is what makes an auto trajectory
    # array-equal to its recorded static plan sequence (module docstring).
    # ------------------------------------------------------------------
    if scheduling:
        every = opts.schedule_every
        n_windows = max(1, -(-opts.iter_max // every))
        ladders = _auto_ladders(opts)
        n_ladders = len(ladders)
        n_plans = 2 * n_ladders
        # effective ladder lengths (0 = the full ls_iters ladder, last) —
        # ascending, for the smallest-candidate-covering-target search
        eff_arr = jnp.asarray(
            [L if L > 0 else opts.ls_iters for L in ladders], jnp.int32)
        act_thresh = opts.auto_active_frac * B
        if opts.schedule == "replay":
            if opts.schedule_plans is None:
                raise ValueError(
                    "schedule='replay' needs schedule_plans (one plan index "
                    "per window — see schedule_trace_plans())")
            plans_seq = tuple(int(p) for p in opts.schedule_plans)
            if len(plans_seq) < n_windows:
                raise ValueError(
                    f"schedule_plans has {len(plans_seq)} entries; "
                    f"iter_max={opts.iter_max} at schedule_every={every} "
                    f"needs {n_windows}")
            if any(p < 0 or p >= n_plans for p in plans_seq):
                raise ValueError(
                    f"schedule_plans entries must be in [0, {n_plans}) for "
                    f"this plan lattice (got {plans_seq})")
            plans_arr = jnp.asarray(plans_seq[:n_windows], jnp.int32)

        # one step variant per candidate ladder; everything else (bobj,
        # strategy, stop protocol) is shared with the static paths.
        # The plan/gather closures below (fresh_aux / inner / the dyn
        # executors) deliberately MIRROR the static schedules' machinery
        # above (fresh_inner_aux / inner_sweep / repacked / compacted),
        # differing only in closing over step_L[L] instead of step_chunk:
        # the two copies must stay in lockstep for the auto==static parity
        # argument, which tests/test_autoschedule.py enforces by replay.
        step_L = {
            L: functools.partial(
                step_impl, bobj, bstrategy,
                dataclasses.replace(opts, ladder_len=L))
            for L in ladders
        }
        sbuckets = _compaction_buckets(group)
        splan_one = functools.partial(
            _compaction_plan, buckets=jnp.asarray(sbuckets, jnp.int32))
        if chunked:
            scbuckets = _compaction_buckets(n_chunks)
            scbuckets_arr = jnp.asarray(scbuckets, jnp.int32)
            sgplan = functools.partial(_repack_plan, chunk=C,
                                       cbuckets=scbuckets_arr)
            splan_fn = jax.vmap(splan_one)

            def fresh_aux(ls):
                # repack plan over the flattened lanes + per-chunk
                # compaction plans of the repacked layout (same recipe as
                # the static repack+compact schedule's refresh)
                act = _active_mask(ls).reshape(-1)
                gperm, gcidx = sgplan(act)
                gact = jnp.take(act, gperm).reshape(n_chunks, C)
                cperm, cbidx = splan_fn(gact)
                return (gperm, gcidx, cperm, cbidx)

            def make_static_exec(L):
                step = step_L[L]

                def ex(operands):
                    ls, _ = operands
                    new, rows, hist = jax.lax.map(step, ls)
                    return (new, jnp.sum(rows), trips_static,
                            jnp.sum(hist, axis=0))

                return ex

            def make_dyn_exec(L):
                step = step_L[L]

                def inner(sub, inner_aux, m):
                    cperm, cbidx = inner_aux
                    new, rows, hist = jax.lax.map(
                        lambda args: _compacted_sweep(step, sbuckets, *args),
                        (sub, cperm[:m], cbidx[:m]),
                    )
                    return new, jnp.sum(rows), jnp.sum(hist, axis=0)

                def ex(operands):
                    ls, aux = operands
                    new, rows, hist = _repacked_sweep(
                        inner, scbuckets, C, ls, aux[0], aux[1], aux[2:])
                    return new, rows, scbuckets_arr[aux[1]], hist

                return ex
        else:
            def fresh_aux(ls):
                return splan_one(_active_mask(ls))

            def make_static_exec(L):
                step = step_L[L]

                def ex(operands):
                    ls, _ = operands
                    new, rows, hist = step(ls)
                    return new, rows, trips_static, hist

                return ex

            def make_dyn_exec(L):
                step = step_L[L]

                def ex(operands):
                    ls, aux = operands
                    perm, bidx = aux
                    new, rows, hist = _compacted_sweep(step, sbuckets, ls,
                                                       perm, bidx)
                    return new, rows, trips_static, hist

                return ex

        # plan index p = dyn · n_ladders + ladder_idx (auto_plan_lattice)
        executors = ([make_static_exec(L) for L in ladders]
                     + [make_dyn_exec(L) for L in ladders])

        def controller(astate, lanes):
            """New plan from the window's signals (module docstring): latch
            the dynamic plan on the LOCAL active count (per-shard, no
            collective) and re-target the ladder at the smallest candidate
            covering p90 of the window's accepted rungs. The ladder
            hysteresis is ASYMMETRIC, at candidate granularity: a SHORTER
            candidate is adopted immediately — per-sweep ladder rows are
            max(L, maxrung+1)+1, monotone in L, so shortening can never
            cost rows; the only risk is extra one-rung fallback launches
            for a window if the histogram was transiently optimistic —
            while a LONGER candidate (the launch-saving, rows-paying
            direction) needs two consecutive windows mapping to the same
            candidate before it is adopted. That keeps a noisy histogram
            from oscillating the ladder upward while letting the
            controller track a calming swarm at window latency (a
            symmetric two-window rule measurably sat on the expensive
            startup ladder through rosenbrock's whole chaotic phase)."""
            act = jnp.sum(_active_mask(lanes).astype(jnp.int32))
            dyn_on = jnp.logical_or(astate.dyn_on, act < act_thresh)
            total = jnp.sum(astate.hist)
            csum = jnp.cumsum(astate.hist)
            need = (9 * total + 9) // 10  # ceil(0.9 · total)
            r90 = jnp.argmax(csum >= need).astype(jnp.int32)
            target = r90 + 1  # rungs needed to cover p90 speculatively
            lidx = jnp.minimum(
                jnp.searchsorted(eff_arr, target).astype(jnp.int32),
                n_ladders - 1)
            cur = astate.plan % n_ladders
            stable_up = jnp.logical_and(lidx > cur,
                                        lidx == astate.prev_lidx)
            adopt = jnp.logical_and(total > 0,
                                    jnp.logical_or(lidx < cur, stable_up))
            new_lidx = jnp.where(adopt, lidx, cur)
            return astate._replace(
                plan=(jnp.where(dyn_on, n_ladders, 0)
                      + new_lidx).astype(jnp.int32),
                dyn_on=dyn_on,
                prev_lidx=jnp.where(total > 0, lidx, astate.prev_lidx),
                hist=jnp.zeros_like(astate.hist),  # window accumulator reset
            )

        @_in_phase2
        def sched_body(carry):
            k = carry.k
            lanes, rkey, n_restarts, rrows, force = _prologue(carry)
            astate, aux = carry.astate, carry.aux
            w = k // every
            boundary = (k % every) == 0
            if opts.schedule == "replay":
                decided = astate._replace(
                    plan=plans_arr[w], hist=jnp.zeros_like(astate.hist))
            elif cost_model:
                # the HOST already wrote this window's plan/dyn_on/
                # prev_lidx into the carry at the segment boundary (the
                # cost-model driver below); in-graph the boundary only
                # resets the window histogram — structurally the replay
                # branch with the plan coming from the carry instead of
                # plans_arr, which is what keeps a cost-model run
                # replayable array-equal from its recorded trace
                decided = astate._replace(hist=jnp.zeros_like(astate.hist))
            else:
                decided = controller(astate, lanes)
            # the decision (and the window-histogram reset) lands only on
            # boundary sweeps; in between the stored plan keeps running
            astate = jax.tree.map(
                lambda n, o: jnp.where(boundary, n, o), decided, astate)
            trace = astate.trace.at[w, astate.plan].add(
                boundary.astype(jnp.int32))
            # gather plans refresh at every boundary whose (just-decided)
            # plan is dynamic — static executors never read aux, and
            # dynamic windows always refresh because the decision precedes
            # this refresh, so a static→dynamic switch sees a current
            # layout; stored plans stay valid in between ONLY while the
            # active set shrinks, so a quarantine re-admission or an
            # elastic restore (`force`) refreshes mid-window too
            aux = jax.lax.cond(
                jnp.logical_and(jnp.logical_or(boundary, force),
                                astate.plan >= n_ladders),
                fresh_aux, lambda ls: aux, lanes)
            lanes, srows, strips, shist = jax.lax.switch(
                astate.plan, executors, (lanes, aux))
            astate = astate._replace(hist=astate.hist + shist, trace=trace)
            if injecting:
                lanes = apply_faults(k, lanes)
            n_conv, n_act = counts(lanes, n_restarts)
            return EngineCarry(
                k=k + 1, lanes=lanes, n_conv=n_conv, n_act=n_act, aux=aux,
                rows=carry.rows + rrows + srows,
                trips=carry.trips + strips, astate=astate, rkey=rkey,
                n_restarts=n_restarts, replan=jnp.zeros((), bool),
                deadline=carry.deadline, telem=carry.telem)

        astate0 = _AutoState(
            plan=jnp.asarray(n_ladders - 1, jnp.int32),  # full-ladder static
            dyn_on=jnp.zeros((), bool),
            # -1 never matches a candidate, so the (guarded) lengthening
            # direction needs two real windows of histogram; shortening
            # from the full-ladder startup doesn't consult it
            prev_lidx=jnp.asarray(-1, jnp.int32),
            hist=jnp.zeros((opts.ls_iters + 1,), jnp.int32),
            trace=jnp.zeros((n_windows, n_plans), jnp.int32),
        )
        make_aux0 = fresh_aux
        if cost_model:
            from repro.launch import telemetry as _telemetry
            telem0 = _telemetry.telemetry_init(n_windows,
                                               opts.telemetry_costs)
        else:
            telem0 = ()
    else:
        astate0 = ()
        telem0 = ()

    # ------------------------------------------------------------------
    # Quarantine/retry + deterministic fault injection (DESIGN.md §15).
    # Both address lanes on the FLATTENED lane axis (0..B_flat-1).
    # ------------------------------------------------------------------
    def _flat(ls):
        if chunked:
            return jax.tree.map(
                lambda a: a.reshape((B_flat,) + a.shape[2:]), ls)
        return ls

    def _unflat(ls):
        if chunked:
            return jax.tree.map(
                lambda a: a.reshape((n_chunks, C) + a.shape[1:]), ls)
        return ls

    if retrying:
        def retry_pass(lanes, rkey, n_restarts):
            """Heal failed lanes with budget left: re-seed x, re-init the
            lane through the same batched init as solve start (fresh
            identity-H direction state, fresh converged/failed flags), and
            charge the re-init's eval cost. Runs under lax.cond so sweeps
            with nothing to heal skip the whole pass."""
            flat = _flat(lanes)
            eligible = jnp.logical_and(
                flat.failed,
                jnp.logical_and(jnp.logical_not(is_pad_flat),
                                n_restarts < opts.retry_budget))
            any_r = jnp.any(eligible)

            def heal(flat, rkey, n_restarts):
                key = jax.random.wrap_key_data(rkey)
                key, sub = jax.random.split(key)
                if opts.retry_mode == "uniform":
                    lo, hi = opts.retry_bounds
                    X = faults_reseed(sub, flat.x, eligible, lo, hi)
                else:
                    # perturb the lane's own iterate; a NaN-poisoned
                    # iterate is re-centered (bounds midpoint, else 0)
                    mid = (0.5 * (opts.retry_bounds[0]
                                  + opts.retry_bounds[1])
                           if opts.retry_bounds is not None else 0.0)
                    base = jnp.where(jnp.isfinite(flat.x), flat.x, mid)
                    noise = opts.retry_sigma * jax.random.normal(
                        sub, flat.x.shape, flat.x.dtype)
                    X = jnp.where(eligible[:, None], base + noise, flat.x)
                fresh = batch_lanes_init(bobj, bstrategy, X, opts.theta)

                def sel(n, o):
                    e = eligible.reshape(
                        eligible.shape + (1,) * (n.ndim - 1))
                    return jnp.where(e, n, o)

                merged = jax.tree.map(sel, fresh, flat)
                # eval counters are cumulative across a lane's lives: the
                # re-init's cost ADDS to the history instead of resetting
                merged = merged._replace(
                    n_evals=flat.n_evals
                    + jnp.where(eligible, fresh.n_evals, 0))
                return (merged, jax.random.key_data(key),
                        n_restarts + eligible.astype(jnp.int32),
                        jnp.asarray(B_flat, jnp.int32))

            def skip(flat, rkey, n_restarts):
                return flat, rkey, n_restarts, jnp.zeros((), jnp.int32)

            flat, rkey, n_restarts, rrows = jax.lax.cond(
                any_r, heal, skip, flat, rkey, n_restarts)
            return _unflat(flat), rkey, n_restarts, rrows, any_r

    if injecting:
        def apply_faults(k, lanes):
            """Post-sweep injections from the fault plan, keyed on the
            carried sweep counter k (deterministic under jit and across
            resume). NaN injection simulates a numeric escape (g <- NaN,
            failed); kill freezes the lane as failed with state intact.
            Padding lanes are never targeted."""
            flat = _flat(lanes)
            nan_m, kill_m = faults_masks(fault_plan, k, B_flat)
            nan_m = jnp.logical_and(nan_m, jnp.logical_not(is_pad_flat))
            kill_m = jnp.logical_and(kill_m, jnp.logical_not(is_pad_flat))
            flat = flat._replace(
                g=jnp.where(nan_m[:, None],
                            jnp.full_like(flat.g, jnp.nan), flat.g),
                failed=jnp.logical_or(flat.failed,
                                      jnp.logical_or(nan_m, kill_m)),
            )
            return _unflat(flat)

    def counts(lanes, n_restarts):
        """Global (converged, active) lane counts. The collective (when the
        distributed driver passes a psum) lives in the loop *body*, so the
        while cond only reads replicated scalars from the carry. A failed
        lane with retry budget left counts as ACTIVE: the stop protocol
        must not exit the loop with heals still pending."""
        n_conv = count(jnp.sum(lanes.converged.astype(jnp.int32)))
        act = _active_mask(lanes).reshape(-1)
        if retrying:
            act = jnp.logical_or(
                act,
                jnp.logical_and(
                    lanes.failed.reshape(-1),
                    jnp.logical_and(jnp.logical_not(is_pad_flat),
                                    n_restarts < opts.retry_budget)))
        n_act = count(jnp.sum(act.astype(jnp.int32)))
        return n_conv, n_act

    def _prologue(carry):
        """Start-of-sweep healing: quarantined lanes with budget left are
        re-seeded BEFORE the sweep runs, so the sweep that follows already
        steps the healed lane. Returns `force` = the gather plans must be
        refreshed off-boundary (re-admission / elastic restore broke the
        active-set-only-shrinks invariant the stored plans rely on)."""
        lanes, rkey, n_restarts = carry.lanes, carry.rkey, carry.n_restarts
        rrows = jnp.zeros((), jnp.int32)
        force = carry.replan
        if deadlining:
            # deadline expiry: a lane whose budget is spent freezes as
            # failed before this sweep steps it, so an admit(deadline=k0+m)
            # lane runs exactly m sweeps — the solo-solve iterate count.
            # No plan force needed: expiry only SHRINKS the active set,
            # which is the invariant stored gather plans rely on.
            flatl = _flat(lanes)
            expired = jnp.logical_and(
                jnp.logical_and(carry.deadline > 0,
                                carry.k >= carry.deadline),
                jnp.logical_not(jnp.logical_or(flatl.converged,
                                               flatl.failed)))
            lanes = _unflat(flatl._replace(
                failed=jnp.logical_or(flatl.failed, expired)))
        if retrying:
            lanes, rkey, n_restarts, rrows, retried = retry_pass(
                lanes, rkey, n_restarts)
            force = jnp.logical_or(force, retried)
        return lanes, rkey, n_restarts, rrows, force

    @_in_phase2
    def cond(carry):
        return jnp.logical_and(
            carry.k < opts.iter_max,
            jnp.logical_and(carry.n_conv < required_c, carry.n_act > 0),
        )

    @_in_phase2
    def body(carry):
        k = carry.k
        lanes, rkey, n_restarts, rrows, force = _prologue(carry)
        aux = carry.aux
        if repacking:
            aux = refresh_plans(k, lanes, aux, force)
            lanes, srows, strips = repacked(lanes, aux)
        elif compacting:
            # refresh the partition/bucket on boundary sweeps only — under
            # lax.cond the plan (argsort + bucket search) is actually
            # skipped in between, which is what lets compact_every > 1
            # amortize it; the stored plan stays valid meanwhile (the
            # active set only shrinks, except under `force`)
            renew = jnp.logical_or((k % opts.compact_every) == 0, force)
            aux = jax.lax.cond(
                renew,
                lambda ls, a: plan_fn(_active_mask(ls)),
                lambda ls, a: a,
                lanes, aux,
            )
            perm, bidx = aux
            lanes, srows = compacted(lanes, perm, bidx)
            strips = trips_static
        else:
            lanes, srows, _ = sweep(lanes)
            strips = trips_static
        if injecting:
            lanes = apply_faults(k, lanes)
        n_conv, n_act = counts(lanes, n_restarts)
        return EngineCarry(
            k=k + 1, lanes=lanes, n_conv=n_conv, n_act=n_act, aux=aux,
            rows=carry.rows + rrows + srows, trips=carry.trips + strips,
            astate=carry.astate, rkey=rkey, n_restarts=n_restarts,
            replan=jnp.zeros((), bool), deadline=carry.deadline,
            telem=carry.telem)

    # raw uint32 key data, not a typed key: snapshots np.asarray it and
    # shard_map moves it across the mesh boundary, neither of which typed
    # PRNG key arrays support cleanly
    if retry_key is None:
        retry_key = jax.random.key(0)
    if jnp.issubdtype(jnp.asarray(retry_key).dtype, jax.dtypes.prng_key):
        rkey0 = jax.random.key_data(retry_key)
    else:
        rkey0 = jnp.asarray(retry_key, jnp.uint32)

    @_in_phase2
    def make_carry0(X=None, rk=None):
        # the optional args exist for the hosted driver's cross-call jit
        # cache (start values become traced inputs instead of baked
        # constants); every in-graph caller uses the no-arg closure form
        lanes = init_lanes(X)
        n_restarts0 = jnp.zeros((B_flat,), jnp.int32)
        n_conv0, n_act0 = counts(lanes, n_restarts0)
        return EngineCarry(
            k=jnp.zeros((), jnp.int32), lanes=lanes, n_conv=n_conv0,
            n_act=n_act0, aux=make_aux0(lanes), rows=eval_rows0,
            trips=jnp.zeros((), jnp.int32), astate=astate0,
            rkey=rkey0 if rk is None else rk,
            n_restarts=n_restarts0, replan=jnp.zeros((), bool),
            deadline=jnp.zeros((B_flat,), jnp.int32), telem=telem0)

    @_in_phase2
    def finalize(carry):
        k, lanes = carry.k, carry.lanes
        schedule_trace = carry.astate.trace if scheduling else None
        if chunked:
            lanes = jax.tree.map(
                lambda a: a.reshape((-1,) + a.shape[2:])[:B], lanes
            )
        status = jnp.where(
            lanes.converged,
            CONVERGED,
            jnp.where(
                jnp.logical_or(lanes.failed, k >= opts.iter_max),
                DIVERGED, STOPPED
            ),
        ).astype(jnp.int32)
        return BFGSResult(
            x=lanes.x,
            fval=lanes.f,
            grad_norm=jax.vmap(jnp.linalg.norm)(lanes.g),
            status=status,
            iterations=k,
            n_converged=jnp.sum(lanes.converged.astype(jnp.int32)),
            n_evals=lanes.n_evals,
            eval_rows=carry.rows,
            map_trips=carry.trips,
            schedule_trace=schedule_trace,
            n_restarts=carry.n_restarts[:B],
            n_failed=jnp.sum(lanes.failed.astype(jnp.int32)),
            telemetry=carry.telem if cost_model else None,
        )

    # ------------------------------------------------------------------
    # Lane admission/retirement as first-class carry events (DESIGN.md
    # §16). These are the solve service's hooks: `admit_lanes` seeds fresh
    # lanes into chosen flat slots of a LIVE carry (generalizing the
    # quarantine heal in retry_pass — same full-batch re-init through
    # init_lanes, same per-leaf where-merge, same replan forcing so the
    # repack/compact/auto-schedule machinery sees an admission exactly
    # like a retry), `vacate_lanes` turns a fresh carry into an empty
    # pool, and `lane_view` is the per-slot harvest read at a segment
    # boundary.
    # ------------------------------------------------------------------
    @_in_phase2
    def admit_lanes(carry, mask, X, deadlines):
        """Seed fresh lanes at X rows into the mask'd flat slots.

        mask: (B_flat,) bool — slots to (re)start; padding is never
        admitted. X: (B, D) start points (only mask'd rows are read).
        deadlines: (B_flat,) int32 absolute sweep deadlines (0 = none).
        Fresh lanes get reset n_evals/n_restarts — each admission is a new
        solve, not a new life of an old one — so harvested counters match
        a solo run's exactly."""
        mask = jnp.logical_and(mask, jnp.logical_not(is_pad_flat))
        fresh = _flat(init_lanes(X))
        flat = _flat(carry.lanes)

        def sel(n, o):
            m = mask.reshape(mask.shape + (1,) * (n.ndim - 1))
            return jnp.where(m, n, o)

        lanes = _unflat(jax.tree.map(sel, fresh, flat))
        n_restarts = jnp.where(mask, 0, carry.n_restarts).astype(jnp.int32)
        deadline = jnp.where(mask, deadlines,
                             carry.deadline).astype(jnp.int32)
        n_conv, n_act = counts(lanes, n_restarts)
        any_m = jnp.any(mask)
        return carry._replace(
            lanes=lanes, n_conv=n_conv, n_act=n_act,
            rows=carry.rows + jnp.where(any_m, eval_rows0, 0),
            n_restarts=n_restarts, deadline=deadline,
            replan=jnp.logical_or(carry.replan, any_m))

    @_in_phase2
    def vacate_lanes(carry):
        """Freeze every slot (failed, not converged): the service's empty
        initial pool. Admissions then light slots back up one by one."""
        flat = _flat(carry.lanes)
        flat = flat._replace(
            converged=jnp.zeros_like(flat.converged),
            failed=jnp.ones_like(flat.failed))
        lanes = _unflat(flat)
        n_conv, n_act = counts(lanes, carry.n_restarts)
        return carry._replace(lanes=lanes, n_conv=n_conv, n_act=n_act)

    @_in_phase2
    def lane_view(carry):
        """Flat per-slot harvest view. grad_norm is computed on-device the
        same way finalize's is, so a harvested result is array-equal to
        the solo solve's BFGSResult fields."""
        flat = _flat(carry.lanes)
        return {
            "k": carry.k,
            "x": flat.x,
            "f": flat.f,
            "grad_norm": jax.vmap(jnp.linalg.norm)(flat.g),
            "converged": flat.converged,
            "failed": flat.failed,
            "n_evals": flat.n_evals,
            "deadline": carry.deadline,
        }

    step_body = sched_body if scheduling else body

    if _as_program:
        return MultistartProgram(make_carry0=make_carry0, cond=cond,
                                 body=step_body, finalize=finalize,
                                 opts=opts, required_c=required_c)

    if not hosted and not _as_host:
        with jax.named_scope("zeus.phase2"):
            return finalize(jax.lax.while_loop(cond, step_body, make_carry0()))

    # ------------------------------------------------------------------
    # Host-segmented driver (checkpoint / preempt / resume): run the SAME
    # cond/body as segments of lax.while_loop bounded at the next host
    # boundary (checkpoint cadence, preemption sweep), with np snapshots
    # through checkpoint/manager.py in between. Resume is array-equal
    # because the sweeps replayed from a snapshot read nothing outside
    # the carry (DESIGN.md §15).
    # ------------------------------------------------------------------
    from repro.checkpoint import manager as ckpt_manager

    # Cache the jitted init/segment/finalize across run_multistart calls:
    # each call builds fresh closures, and without the cache every solve
    # would re-trace + recompile them — the checkpoint-overhead gate
    # (BENCH_CHECKPOINT_CEIL vs the once-jitted in-device loop) measures
    # steady-state snapshot cost, not compile churn. Keyed on everything
    # the traced computation can depend on; start values and retry keys
    # are traced INPUTS of the cached init, never baked constants.
    cache_key = ("hosted", _hashable(f), type(strategy),
                 _freeze_config(strategy), opts, x0.shape, str(x0.dtype),
                 None if pcount is None else _hashable(pcount))
    cached = _HOSTED_JIT_CACHE.get(cache_key)
    if cached is None:
        cached = (
            jax.jit(lambda X, rk: make_carry0(X, rk)),
            jax.jit(_in_phase2(lambda c, k_end: jax.lax.while_loop(
                lambda cc: jnp.logical_and(cond(cc), cc.k < k_end),
                step_body, c))),
            jax.jit(finalize),
            # the loop evaluates cond on the host between segments; eager
            # op-by-op dispatch of its reductions costs more than the
            # segment itself at small cells, so it is jitted too
            jax.jit(cond),
            # solve-service hooks: mid-flight admission, empty-pool
            # vacate, and the boundary harvest view (DESIGN.md §16)
            jax.jit(admit_lanes),
            jax.jit(vacate_lanes),
            jax.jit(lane_view),
            # cost-model boundary signal: the same LOCAL active count the
            # in-graph controller latches dyn_on from (traced lazily, so
            # non-scheduling solves never touch it)
            jax.jit(lambda c: jnp.sum(
                _active_mask(c.lanes).astype(jnp.int32))),
        )
        _HOSTED_JIT_CACHE[cache_key] = cached
    (carry0_jit, seg, fin, cond_jit, admit_jit, vacate_jit, view_jit,
     act_jit) = cached

    if _as_host:
        return HostedSolve(
            _carry0=carry0_jit, _seg=seg, _fin=fin, _cond=cond_jit,
            _admit=admit_jit, _vacate=vacate_jit, _view=view_jit,
            opts=opts, B=B, B_flat=B_flat, dim=D, required_c=required_c,
            _x0=jnp.asarray(x0), _rkey0=rkey0)

    if resume_from is not None:
        # eval_shape: restore needs only the carry's structure/dtypes, and
        # skipping the real init skips its B objective evaluations
        like = jax.eval_shape(make_carry0)
        carry = ckpt_manager.restore(resume_from, like)
    else:
        carry = carry0_jit(x0, rkey0)

    # Snapshot writes run on a single background thread: the npz write +
    # COMMIT rename overlap the next segment's compute, leaving only the
    # host gather on the critical path. At most one write is in flight —
    # the writer is joined before the next save, before a Preempted raise,
    # and before returning, so manager.latest_step is deterministic at
    # every boundary a caller (or the resume parity suite) can observe.
    pending: list = []

    def _join_writer():
        if pending:
            t, err = pending.pop()
            t.join()
            if err:
                raise err[0]

    def _save_async(c):
        _join_writer()
        host = jax.device_get(c)
        err: list = []

        def _write():
            try:
                ckpt_manager.save(opts.checkpoint_dir, int(host.k), host,
                                  keep=opts.checkpoint_keep)
            except BaseException as e:  # surfaced at the next join
                err.append(e)

        t = threading.Thread(target=_write, daemon=True)
        t.start()
        pending.append((t, err))

    every_ck = opts.checkpoint_every
    if cost_model:
        # host side of the cost-model controller (DESIGN.md §17): at each
        # schedule_every boundary, score the plan lattice in measured
        # seconds and write the decision into the carry BEFORE the
        # boundary segment runs — sched_body's cost-model branch then
        # executes (and traces) the written plan exactly like replay
        # executes plans_arr. Segments are clamped to window boundaries
        # so each wall measurement covers whole windows of one plan.
        eff_lens = [L if L > 0 else opts.ls_iters for L in ladders]
        fixed_costs = opts.telemetry_costs is not None
        eprobe = _telemetry.probe_energy()
    while bool(cond_jit(carry)):
        k_now = int(carry.k)
        if preempt_at is not None and k_now >= preempt_at:
            # adversarial death at a sweep boundary: NOTHING past the last
            # cadence snapshot is saved (the resume parity suite relies on
            # the lost tail being replayed exactly)
            _join_writer()
            raise Preempted(k_now, opts.checkpoint_dir)
        if cost_model and k_now % every == 0:
            astate = carry.astate
            plan, prev_lidx, dyn_on = _telemetry.cost_model_decision(
                jax.device_get(astate.hist), int(act_jit(carry)), eff_lens,
                int(astate.plan), int(astate.prev_lidx),
                bool(astate.dyn_on), act_thresh=act_thresh,
                c_row=float(np.asarray(carry.telem.c_row)),
                c_launch=float(np.asarray(carry.telem.c_launch)))
            carry = carry._replace(astate=astate._replace(
                plan=jnp.asarray(plan, jnp.int32),
                prev_lidx=jnp.asarray(prev_lidx, jnp.int32),
                dyn_on=jnp.asarray(dyn_on, bool)))
        k_end = opts.iter_max
        if every_ck:
            k_end = min(k_end, (k_now // every_ck + 1) * every_ck)
        if cost_model:
            k_end = min(k_end, (k_now // every + 1) * every)
        if preempt_at is not None:
            k_end = min(k_end, preempt_at)
        if cost_model:
            rows0, trips0 = int(carry.rows), int(carry.trips)
            e0 = eprobe.read_j()
            t0 = time.perf_counter()
            carry = jax.block_until_ready(
                seg(carry, jnp.asarray(k_end, jnp.int32)))
            wall = time.perf_counter() - t0
            e1 = eprobe.read_j()
            de = e1 - e0 if e0 is not None and e1 is not None else None
            # the window is complete when the segment reached its
            # boundary OR the solve just stopped (early-converged final
            # partial windows still feed the fit — their plan ran for
            # every sweep that executed)
            done = (int(carry.k) % every == 0) or not bool(cond_jit(carry))
            carry = carry._replace(telem=_telemetry.record_window(
                carry.telem, k_now // every, wall,
                int(carry.rows) - rows0, int(carry.trips) - trips0,
                energy_j=de, ema=opts.telemetry_ema, fixed=fixed_costs,
                refit=done))
        else:
            carry = seg(carry, jnp.asarray(k_end, jnp.int32))
        if every_ck and (int(carry.k) % every_ck == 0
                         or not bool(cond_jit(carry))):
            _save_async(carry)
    _join_writer()
    return fin(carry)


def open_multistart(
    f: Callable,
    x0: jnp.ndarray,  # (B, D): defines the pool width; values are the
    # placeholder starts empty_carry initializes vacant slots from
    strategy: DirectionStrategy,
    opts: EngineOptions = EngineOptions(),
    pcount: Optional[Callable] = None,
    retry_key: Optional[jnp.ndarray] = None,
) -> HostedSolve:
    """Open a multistart solve under host control instead of running it.

    Returns a HostedSolve whose segment/admit/lane_view hooks let a caller
    (the continuous-batching solve service, serve/service.py) drive the
    SAME cond/body the closed-loop solve runs, harvesting retired lanes
    and seeding queued work into freed slots at segment boundaries.
    Same validation, same jit cache, same carry as run_multistart."""
    return run_multistart(f, x0, strategy, opts, pcount=pcount,
                          retry_key=retry_key, _as_host=True)


# ---------------------------------------------------------------------------
# Solver registry (idiom: models/registry.py). A solver factory maps its own
# options object (or None for defaults) + a lane_chunk override to a ready
# (strategy, EngineOptions) pair, so drivers select solvers by name.
# ---------------------------------------------------------------------------
SolverFactory = Callable[..., Tuple[DirectionStrategy, EngineOptions]]

_SOLVERS: Dict[str, SolverFactory] = {}


def register_solver(name: str):
    """Decorator: `@register_solver("bfgs")` on a factory
    `(solver_opts=None, lane_chunk=None) -> (strategy, EngineOptions)`."""

    def deco(factory: SolverFactory) -> SolverFactory:
        _SOLVERS[name] = factory
        return factory

    return deco


def _ensure_builtin_solvers():
    # the built-in strategies live in their own modules; importing them
    # registers their factories (import cycle-safe: they import engine only)
    from repro.core import bfgs, lbfgs  # noqa: F401


def solver_names() -> Tuple[str, ...]:
    _ensure_builtin_solvers()
    return tuple(sorted(_SOLVERS))


def get_solver(name: str) -> SolverFactory:
    if name not in _SOLVERS:
        _ensure_builtin_solvers()
    if name not in _SOLVERS:
        raise ValueError(
            f"unknown solver {name!r}; registered: {', '.join(sorted(_SOLVERS))}"
        )
    return _SOLVERS[name]
