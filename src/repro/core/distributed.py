"""Distributed ZEUS: the swarm sharded across a (pod, data, model) mesh.

The paper's parallelism is thread-per-optimization on one GPU. At pod scale
the same insight shards the *particle axis* over every mesh axis: each device
owns N/devices lanes and runs the identical program; the only cross-device
traffic per sweep is

  - PSO global best:    one (f, argdevice) min-reduction + one (dim,) bcast,
  - BFGS stop protocol: one int32 psum (converged count) — the TPU analogue
    of the paper's atomicAdd(converged)/stopFlag,

i.e. O(dim) bytes per sweep per device — ZEUS is collective-light by
construction, which is what makes it runnable on thousands of chips.

Fault tolerance (DESIGN.md §15): with `checkpoint_every` / a FaultPlan
preemption / `resume_from`, the phase-2 sweep loop runs HOST-SEGMENTED —
the per-shard engine program (engine.MultistartProgram) advances between
host boundaries under shard_map, and the full EngineCarry (every per-shard
leaf wrapped with a leading shard axis) is snapshotted through
checkpoint/manager.py. Restoring onto the SAME shard count is array-equal;
restoring onto a DIFFERENT shard count (elastic) re-derives the per-shard
wrapped leaves (counters summed into shard 0, controller state broadcast,
gather plans rebuilt via the carry's `replan` flag) and continues the same
global solve. Lane quarantine/retry runs inside the carry on both paths,
with per-shard re-seed streams folded from the solve key.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.engine import BFGSResult, EngineCarry, run_multistart
from repro.core.meanfield import run_meanfield_pso
from repro.core.pso import PSOOptions, SwarmState, init_swarm, pso_step
from repro.core.zeus import (_RETRY_FOLD, PHASE1_STRATEGIES, ZeusOptions,
                             ZeusResult, _phase2_setup, _select_best,
                             phase1_particles, solve_phase2, uniform_starts)


def _axis_index_flat(axis_names: Tuple[str, ...]) -> jnp.ndarray:
    """Flat linear device index across the listed mesh axes."""
    idx = jnp.zeros((), jnp.int32)
    for name in axis_names:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx


def make_pmin(axis_names: Tuple[str, ...]):
    """Deterministic cross-device (value, vector) argmin reduction.

    Replaces the paper's atomicMin race: ties broken by lowest device index,
    so results are bit-reproducible run to run."""

    def pmin(gf: jnp.ndarray, gx: jnp.ndarray):
        gmin = jax.lax.pmin(gf, axis_names)
        me = _axis_index_flat(axis_names)
        big = jnp.iinfo(jnp.int32).max
        winner = jax.lax.pmin(jnp.where(gf == gmin, me, big), axis_names)
        gx_bcast = jax.lax.psum(
            jnp.where(me == winner, gx, jnp.zeros_like(gx)), axis_names
        )
        return gmin, gx_bcast

    return pmin


def make_pcount(axis_names: Tuple[str, ...]):
    def pcount(c: jnp.ndarray):
        return jax.lax.psum(c, axis_names)

    return pcount


def make_pmoments(axis_names: Tuple[str, ...]):
    """Cross-device softmax-moment reduction for the mean-field consensus
    (DESIGN.md §18).

    Each shard hands over its log-sum-exp partials (m, S, N) = (max
    log-weight, Σw, Σw·x with weights shifted by its OWN m). One pmax finds
    the global max log-weight M, each shard re-shifts by exp(m − M) ≤ 1 —
    never an overflow, and exact for the shard that owns the max — and two
    psums reduce the moments. O(D) bytes per device per iteration; the
    consensus x̄ = N/S then comes out bit-identical on every device."""

    def pmoments(m: jnp.ndarray, S: jnp.ndarray, N: jnp.ndarray):
        M = jax.lax.pmax(m, axis_names)
        # an all-non-finite shard has m = -inf (zero partials): keep its
        # scale 0 rather than exp(-inf - -inf) = nan when M is -inf too
        M_safe = jnp.where(jnp.isfinite(M), M, 0.0)
        scale = jnp.exp(jnp.where(jnp.isfinite(m), m - M_safe, -jnp.inf))
        return (jax.lax.psum(scale * S, axis_names),
                jax.lax.psum(scale * N, axis_names))

    return pmoments


def _phase1_shard(
    f: Callable,
    key: jnp.ndarray,
    dim: int,
    lower: float,
    upper: float,
    opts: ZeusOptions,
    axis_names: Tuple[str, ...],
    n_local: int,
):
    """Per-shard phase 1 (zeus.run_phase1 with this shard's lane count and
    the mesh collectives): returns (starts, best_f_seen) with best_f_seen
    replicated across devices. The PSO swarm couples through make_pmin
    (global-best bcast), the mean-field swarm through make_pmoments (the
    two-psum consensus) — each strategy's only cross-device traffic."""
    dtype = jnp.dtype(opts.dtype)
    with jax.named_scope("zeus.phase1"):
        if not opts.use_pso:
            # skip the swarm entirely (phase 1 already costs one objective
            # eval per particle) — same contract as zeus()
            return uniform_starts(key, n_local, dim, lower, upper, dtype)
        if opts.phase1 == "meanfield":
            mf_opts = dataclasses.replace(opts.meanfield, n_particles=n_local)
            mf = run_meanfield_pso(f, key, dim, lower, upper, mf_opts,
                                   pmoments=make_pmoments(axis_names),
                                   dtype=dtype)
            # gf is a shard-local running min (reporting only, never part of
            # the dynamics) — replicate it once at the end
            return mf.x, jax.lax.pmin(mf.gf, axis_names)
        pmin = make_pmin(axis_names)
        state = init_swarm(f, key, n_local, dim, lower, upper, pmin, dtype)
        state = jax.lax.fori_loop(
            0, opts.pso.iter_pso,
            lambda _, s: pso_step(f, s, opts.pso, lower, upper, pmin), state)
        return state.x, state.gf


def _local_zeus(
    f: Callable,
    key: jnp.ndarray,
    dim: int,
    lower: float,
    upper: float,
    opts: ZeusOptions,
    axis_names: Tuple[str, ...],
    n_local: int,
):
    """Per-device shard program (runs under shard_map)."""
    pmin = make_pmin(axis_names)
    pcount = make_pcount(axis_names)

    # decorrelate per-device RNG streams
    key = jax.random.fold_in(key[0], _axis_index_flat(axis_names))

    starts, pso_gf = _phase1_shard(f, key, dim, lower, upper, opts,
                                   axis_names, n_local)

    # phase 2 through the engine: the registry-selected strategy runs with
    # the global stop protocol (pcount = psum over the mesh), per-device
    # chunked lanes when opts.lane_chunk is set, and a per-shard quarantine
    # re-seed stream folded from this shard's (already device-folded) key
    res = solve_phase2(f, starts, opts, pcount=pcount,
                       retry_key=jax.random.fold_in(key, _RETRY_FOLD),
                       bounds=(lower, upper))
    # make the scalar diagnostics truly replicated across devices;
    # eval_rows sums the physical batched-sweep rows over the mesh (0 under
    # per_lane) and map_trips the per-shard chunk-step trips — each shard
    # repacks/compacts its own lanes, so the psum'd totals surface the
    # whole-mesh tail work. The schedule trace psums the per-window plan
    # choices the same way: the auto controller decides per shard (its
    # signals are local, collective-free), so row w of the psum'd trace
    # reads "how many shards ran plan p in window w".
    with jax.named_scope("zeus.finale"):
        res = res._replace(n_converged=pcount(res.n_converged),
                           eval_rows=pcount(res.eval_rows),
                           map_trips=pcount(res.map_trips),
                           n_failed=pcount(res.n_failed),
                           schedule_trace=(pcount(res.schedule_trace)
                                           if res.schedule_trace is not None
                                           else None))

        # global best among converged lanes
        best_x, best_f = _select_best(res)
        best_f, best_x = pmin(best_f, best_x)
        return best_x, best_f, res, pso_gf


def distributed_zeus(
    f: Callable,
    dim: int,
    lower: float,
    upper: float,
    opts: ZeusOptions,
    mesh: Mesh,
) -> Callable:
    """Build the pjit-able distributed ZEUS for `mesh`.

    Returns a function of `key` (a (1,)-keyed array so shard_map can
    replicate it) producing a ZeusResult whose `raw` lanes stay sharded
    across the mesh (lane axis = all mesh axes flattened).
    """
    axis_names = tuple(mesh.axis_names)
    n_devices = int(np.prod(mesh.devices.shape))
    if opts.phase1 not in PHASE1_STRATEGIES:
        raise ValueError(
            f"unknown phase1 strategy {opts.phase1!r}; expected one of "
            f"{PHASE1_STRATEGIES}")
    # lane count of the ACTIVE phase-1 strategy (pso or meanfield swarm)
    n_total = phase1_particles(opts)
    if n_total % n_devices:
        raise ValueError(
            f"n_particles={n_total} must divide over {n_devices} devices"
        )
    n_local = n_total // n_devices
    dtype = jnp.dtype(opts.dtype)

    # whether the engine will emit a ScheduleTrace decides the out-spec
    # pytree's shape (None leaves are empty nodes under shard_map)
    strategy, eopts = _phase2_setup(opts)
    if eopts.retry_bounds is None:
        eopts = dataclasses.replace(
            eopts, retry_bounds=(float(lower), float(upper)))
    traced_schedule = eopts.schedule in ("auto", "replay")
    ck_every = eopts.checkpoint_every
    ck_dir = eopts.checkpoint_dir
    preempt_at = (eopts.fault_plan.preempt_at_sweep
                  if eopts.fault_plan is not None else None)
    required_c_eff = (eopts.required_c if eopts.required_c is not None
                      else n_local)

    lane_spec = P(axis_names)  # lane axis sharded over all mesh axes
    res_specs = BFGSResult(
        x=lane_spec,
        fval=lane_spec,
        grad_norm=lane_spec,
        status=lane_spec,
        iterations=P(),
        n_converged=P(),
        n_evals=lane_spec,
        eval_rows=P(),
        map_trips=P(),
        # psum'd per-window plan counts, replicated like the other
        # whole-mesh diagnostics
        schedule_trace=P() if traced_schedule else None,
        n_restarts=lane_spec,  # per-lane re-seed counts stay sharded
        n_failed=P(),  # psum'd total
        # the telemetry cost model is host-in-the-loop and unavailable
        # through the program driver (engine validation), so no shard
        # ever emits one — an empty leaf, like schedule_trace off
        telemetry=None,
    )
    out_specs = (P(), P(), res_specs, P())  # best_x, best_f, res, pso gf

    local = functools.partial(
        _local_zeus,
        f,
        dim=dim,
        lower=lower,
        upper=upper,
        opts=opts,
        axis_names=axis_names,
        n_local=n_local,
    )

    sharded = jax.shard_map(
        lambda key: local(key),
        mesh=mesh,
        in_specs=(P(),),
        out_specs=out_specs,
        check_vma=False,
    )

    # ------------------------------------------------------------------
    # Host-segmented fault-tolerant path (checkpoint / preempt / resume).
    # The per-shard engine program is rebuilt inside each shard_map from
    # shapes alone; the EngineCarry is the only state crossing segments.
    # Per-shard leaves that are not lane-sharded (counters, plans, PRNG
    # data, controller state) get a leading length-1 shard axis inside the
    # shard ("wrapped"), so the GLOBAL carry stacks them (n_shards, ...)
    # and a snapshot of it is mesh-shape-explicit — which is what makes
    # the elastic restore below possible.
    # ------------------------------------------------------------------
    def _shard_program(x0_local, pcount, retry_key=None):
        return run_multistart(f, x0_local, strategy, eopts, pcount=pcount,
                              retry_key=retry_key, _as_program=True)

    def _wrap(c: EngineCarry) -> EngineCarry:
        w = lambda t: jax.tree.map(lambda a: a[None], t)
        return c._replace(aux=w(c.aux), rows=c.rows[None],
                          trips=c.trips[None], astate=w(c.astate),
                          rkey=c.rkey[None])

    def _unwrap(c: EngineCarry) -> EngineCarry:
        u = lambda t: jax.tree.map(lambda a: a[0], t)
        return c._replace(aux=u(c.aux), rows=c.rows[0], trips=c.trips[0],
                          astate=u(c.astate), rkey=c.rkey[0])

    def _carry_specs(carry_like, leaf):
        # NOTE: never jax.tree.map OVER a spec tree (PartitionSpec is a
        # tuple subclass and would flatten); build spec trees from the
        # carry's structure instead, with `leaf` making the sharded leaves
        sh = lambda t: jax.tree.map(lambda _: leaf(lane_spec), t)
        return EngineCarry(
            k=leaf(P()), lanes=sh(carry_like.lanes), n_conv=leaf(P()),
            n_act=leaf(P()), aux=sh(carry_like.aux), rows=leaf(lane_spec),
            trips=leaf(lane_spec), astate=sh(carry_like.astate),
            rkey=leaf(lane_spec), n_restarts=leaf(lane_spec),
            replan=leaf(P()), deadline=leaf(lane_spec),
            telem=sh(carry_like.telem))

    def init_shard(key):
        pcount = make_pcount(axis_names)
        key = jax.random.fold_in(key[0], _axis_index_flat(axis_names))
        starts, pso_gf = _phase1_shard(f, key, dim, lower, upper, opts,
                                       axis_names, n_local)
        prog = _shard_program(starts, pcount,
                              retry_key=jax.random.fold_in(key, _RETRY_FOLD))
        return _wrap(prog.make_carry0()), pso_gf

    def seg_shard(carry, k_end):
        prog = _shard_program(jnp.zeros((n_local, dim), dtype),
                              make_pcount(axis_names))
        with jax.named_scope("zeus.phase2"):
            c = jax.lax.while_loop(
                lambda cc: jnp.logical_and(prog.cond(cc), cc.k < k_end),
                prog.body, _unwrap(carry))
        return _wrap(c)

    def fin_shard(carry):
        pmin = make_pmin(axis_names)
        pcount = make_pcount(axis_names)
        prog = _shard_program(jnp.zeros((n_local, dim), dtype), pcount)
        res = prog.finalize(_unwrap(carry))
        with jax.named_scope("zeus.finale"):
            res = res._replace(
                n_converged=pcount(res.n_converged),
                eval_rows=pcount(res.eval_rows),
                map_trips=pcount(res.map_trips),
                n_failed=pcount(res.n_failed),
                schedule_trace=(pcount(res.schedule_trace)
                                if res.schedule_trace is not None else None))
            best_x, best_f = _select_best(res)
            best_f, best_x = pmin(best_f, best_x)
            return best_x, best_f, res

    def _elastic_adapt(c: EngineCarry, like_c: EngineCarry, key):
        """Re-derive the wrapped per-shard leaves for a NEW shard count.
        Counters (rows/trips/trace) are mesh totals accumulated per shard
        and psum'd at finalize: summing them into shard 0 preserves every
        total. Controller scalars broadcast from old shard 0 (hist is the
        whole-mesh sum — any deterministic choice works, the next window
        boundary resets it). Gather plans hold LOCAL lane indices and are
        meaningless across a re-shard: they become zeros and the carry's
        `replan` flag forces a refresh before the first resumed sweep.
        Per-shard retry streams are re-derived from the solve key exactly
        as init_shard derives them. Lane leaves are shard-count invariant
        in the flat lane order, but their PHYSICAL layout is not: the
        engine chunks lanes as (n_chunks, C, ...) only while
        lane_chunk < local lane count, so a re-shard can cross the
        chunked/unchunked boundary — re-layout through the flat order."""
        n_new = n_devices
        n_old = int(c.rkey.shape[0])
        n_total = n_local * n_new
        n_loc_old = n_total // n_old
        C = eopts.lane_chunk
        ch_old = C is not None and 0 < C < n_loc_old
        ch_new = C is not None and 0 < C < n_local
        if (ch_old and n_loc_old % C) or (ch_new and n_local % C):
            raise ValueError(
                "elastic restore requires lane_chunk to divide the local "
                f"lane count on both meshes (lane_chunk={C}, "
                f"local lanes {n_loc_old} -> {n_local}): the engine pads "
                "ragged chunks per shard and padding lanes cannot be "
                "re-flattened across a re-shard")

        def relane(a):
            a = np.asarray(a)
            if ch_old:
                a = a.reshape((n_total,) + a.shape[2:])
            if ch_new:
                a = a.reshape((n_total // C, C) + a.shape[1:])
            return jnp.asarray(a)

        lanes = jax.tree.map(relane, c.lanes)

        def sum0(a):
            a = np.asarray(a)
            out = np.zeros((n_new,) + a.shape[1:], a.dtype)
            out[0] = a.sum(axis=0)
            return jnp.asarray(out)

        def bcast0(a):
            a = jnp.asarray(np.asarray(a))
            return jnp.broadcast_to(a[:1], (n_new,) + a.shape[1:])

        astate = c.astate
        if astate != ():
            astate = astate._replace(
                plan=bcast0(astate.plan), dyn_on=bcast0(astate.dyn_on),
                prev_lidx=bcast0(astate.prev_lidx),
                hist=jnp.broadcast_to(
                    jnp.asarray(np.asarray(astate.hist).sum(axis=0)),
                    (n_new,) + astate.hist.shape[1:]),
                trace=sum0(astate.trace))
        rkey = jnp.stack([
            jax.random.key_data(jax.random.fold_in(
                jax.random.fold_in(key, i), _RETRY_FOLD))
            for i in range(n_new)]).astype(c.rkey.dtype)
        return c._replace(
            lanes=lanes,
            aux=jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype),
                             like_c.aux),
            rows=sum0(c.rows), trips=sum0(c.trips), astate=astate,
            rkey=rkey, replan=jnp.ones((), bool))

    def _global_like(n_shards):
        """ShapeDtypeStruct tree of the GLOBAL segmented carry as saved
        from an n_shards-shard mesh. The carry STRUCTURE (repack/compact
        bucket count in aux) depends on the per-shard lane count, so an
        elastic restore must rebuild the like-tree for the snapshot's
        shard count, not the current one. Lane-axis leaves are
        shard-count invariant; wrapped per-shard leaves gain an
        (n_shards, ...) leading axis."""
        n_loc = (n_local * n_devices) // n_shards
        pc = jax.eval_shape(
            lambda x: _shard_program(x, None).make_carry0(),
            jax.ShapeDtypeStruct((n_loc, dim), dtype))
        lane = lambda t: jax.tree.map(lambda l: jax.ShapeDtypeStruct(
            (l.shape[0] * n_shards,) + l.shape[1:], l.dtype), t)
        wrap = lambda t: jax.tree.map(lambda l: jax.ShapeDtypeStruct(
            (n_shards,) + l.shape, l.dtype), t)
        return pc._replace(
            lanes=lane(pc.lanes), aux=wrap(pc.aux), rows=wrap(pc.rows),
            trips=wrap(pc.trips), astate=wrap(pc.astate),
            rkey=wrap(pc.rkey), n_restarts=lane(pc.n_restarts),
            deadline=lane(pc.deadline))

    def _run_segmented(key, resume_from):
        from repro.checkpoint import manager as ckpt_manager
        from repro.launch.faults import Preempted

        like = jax.eval_shape(lambda k: init_sharded(k), key[None])
        carry_like = like[0]
        shardings = (
            _carry_specs(carry_like,
                         lambda s: NamedSharding(mesh, s)),
            NamedSharding(mesh, P()),  # pso_gf (replicated)
        )
        if resume_from is not None:
            meta = ckpt_manager.snapshot_meta(resume_from)

            def _matches(lk):
                ls = jax.tree.leaves(lk)
                return (meta["n_leaves"] == len(ls) and
                        all(list(l.shape) == s
                            for s, l in zip(meta["shapes"], ls)))

            if _matches(like):
                carry, pso_gf = ckpt_manager.restore(resume_from, like)
                if carry.rkey.shape[0] != n_devices:
                    carry = _elastic_adapt(carry, carry_like, key)
            else:
                n_total = n_local * n_devices
                for n_old in range(1, n_total + 1):
                    if n_total % n_old or n_old == n_devices:
                        continue
                    like_old = (_global_like(n_old), like[1])
                    if _matches(like_old):
                        carry, pso_gf = ckpt_manager.restore(
                            resume_from, like_old)
                        carry = _elastic_adapt(carry, carry_like, key)
                        break
                else:
                    raise ValueError(
                        f"checkpoint {resume_from} does not match this "
                        f"solve under any elastic re-shard of its "
                        f"{n_total} lanes — solver/schedule/options "
                        "mismatch")
            carry, pso_gf = jax.device_put((carry, pso_gf), shardings)
        else:
            carry, pso_gf = init_jit(key[None])

        def host_cond(c):
            return (int(c.k) < eopts.iter_max
                    and int(c.n_conv) < required_c_eff
                    and int(c.n_act) > 0)

        while host_cond(carry):
            k_now = int(carry.k)
            if preempt_at is not None and k_now >= preempt_at:
                # adversarial death at a sweep boundary: nothing past the
                # last cadence snapshot survives
                raise Preempted(k_now, ck_dir)
            k_end = eopts.iter_max
            if ck_every:
                k_end = min(k_end, (k_now // ck_every + 1) * ck_every)
            if preempt_at is not None:
                k_end = min(k_end, preempt_at)
            carry = seg_jit(carry, jnp.asarray(k_end, jnp.int32))
            if ck_every and (int(carry.k) % ck_every == 0
                             or not host_cond(carry)):
                ckpt_manager.save(ck_dir, int(carry.k), (carry, pso_gf),
                                  keep=eopts.checkpoint_keep)
        best_x, best_f, res = fin_jit(carry)
        return ZeusResult(
            best_x=best_x, best_f=best_f, raw=res,
            n_converged=res.n_converged, pso_best_f=pso_gf,
            n_failed=res.n_failed, n_restarts=res.n_restarts)

    segmented_cfg = ck_every > 0 or preempt_at is not None
    if segmented_cfg:
        # building the spec trees needs the carry structure, which only
        # depends on shapes — probe it once with a dummy local program
        probe = jax.eval_shape(
            lambda x: _shard_program(x, None).make_carry0(),
            jax.ShapeDtypeStruct((n_local, dim), dtype))
        carry_specs = _carry_specs(
            jax.tree.map(lambda l: l, probe), lambda s: s)
        init_sharded = jax.shard_map(
            init_shard, mesh=mesh, in_specs=(P(),),
            out_specs=(carry_specs, P()), check_vma=False)
        init_jit = jax.jit(init_sharded)
        seg_jit = jax.jit(jax.shard_map(
            seg_shard, mesh=mesh, in_specs=(carry_specs, P()),
            out_specs=carry_specs, check_vma=False))
        fin_jit = jax.jit(jax.shard_map(
            fin_shard, mesh=mesh, in_specs=(carry_specs,),
            out_specs=(P(), P(), res_specs), check_vma=False))

    def run(key: jnp.ndarray,
            resume_from: Optional[str] = None) -> ZeusResult:
        if segmented_cfg or resume_from is not None:
            if not segmented_cfg:
                raise ValueError(
                    "resume_from needs the fault-tolerant driver: set "
                    "checkpoint_every/checkpoint_dir (or a FaultPlan "
                    "preemption) in the options distributed_zeus was "
                    "built with")
            return _run_segmented(key, resume_from)
        best_x, best_f, res, pso_gf = sharded(key[None])
        return ZeusResult(
            best_x=best_x,
            best_f=best_f,
            raw=res,
            n_converged=res.n_converged,
            pso_best_f=pso_gf,
            n_failed=res.n_failed,
            n_restarts=res.n_restarts,
        )

    return run
