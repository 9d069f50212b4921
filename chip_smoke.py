"""Smoke run of the ZEUS solve path on a TPU, with compiled Pallas kernels.

    python chip_smoke.py            # one chip: kernels, solves, service
    python chip_smoke.py --chips 4  # only the distributed solve on a (4,) mesh

Phases, in order (one process, no children):
  1. device check: a TPU, or exit nonzero with no result;
  2. kernel parity: each ops wrapper of the solve path, compiled for the chip
     (its program holds a `tpu_custom_call`), against its kernels/ref.py
     oracle on the same inputs;
  3. paper-scale solve through zeus_jit, sweep_mode="batched": rastrigin
     with 10^5 particles and 5 PSO iterations at D=10, the largest dimension
     of the paper's Fig. 1, and at D=3. Five PSO iterations pull the swarm
     into the basins around its global best, and from D=4 up whether that
     includes x* = 0 depends on the seed (the Fig. 1 collapse); at D=3 it
     holds for every seed tried, so the global-basin check runs there and
     D=10 is checked for a local minimizer;
  4. the same solves with sweep_mode="megakernel", with any fallback to the
     staged path raised as an error, compared with phase 3;
  5. the solve service answering 8 requests of 256 starts on two problems;
  6. (--chips 4 only, and alone) distributed_zeus over four chips against the
     one-chip solve of the phase-3 D=3 problem.

The last line of stdout is {"ok": true, "device": {...}}; it is printed only
when every phase passed. Wall times printed are smoke timings of single
calls, not benchmark measurements.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

N_PARTICLES = 100_000  # the paper's Fig. 1 swarm
ITER_PSO = 5
ITER_BFGS = 100
THETA = 1e-4
LANE_CHUNK = 8192  # keeps the lane-padded (C, 128, 128) H stack at 512 MiB
DIMS = (10, 3)  # paper scale, and the dimension the global-basin check uses
BASIN_DIM = 3
BASIN_TOL = 0.5  # benchmarks/common.n_correct's radius around x* = 0
SERVICE_SLOTS = 1024
SERVICE_REQUESTS = 8
SERVICE_STARTS = 256


def log(msg: str):
    print(msg, flush=True)


def device_check(n_chips: int):
    """Phase 1: a TPU with at least `n_chips` chips, or exit nonzero."""
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {d0.platform!r} "
              f"({d0.device_kind})", file=sys.stderr)
        sys.exit(2)
    if len(devs) < n_chips:
        print(f"chip_smoke: --chips {n_chips} needs {n_chips} devices, "
              f"JAX found {len(devs)}", file=sys.stderr)
        sys.exit(2)
    log(f"[device] platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# -- phase 2: kernel parity ---------------------------------------------------
def require_kernel(exe, what):
    """A compiled Pallas kernel shows as a tpu_custom_call; a jnp reference
    or the interpreter would not."""
    if "tpu_custom_call" not in exe.as_text():
        raise AssertionError(f"{what}: no tpu_custom_call in the program")


def compiled_call(fn, *args):
    """Compile `fn` for the chip, require a Pallas kernel, and run it."""
    exe = jax.jit(fn).lower(*args).compile()
    require_kernel(exe, getattr(fn, "__name__", "kernel"))
    return jax.block_until_ready(exe(*args))


def oracle(fn, *args):
    with jax.default_matmul_precision("highest"):
        return jax.block_until_ready(jax.jit(fn)(*args))


def assert_close(name, got, want, rtol, atol):
    for i, (g, w) in enumerate(zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want))):
        g, w = np.asarray(g), np.asarray(w)
        err = float(np.max(np.abs(g - w)))
        log(f"[kernels] {name}[{i}] shape={g.shape} max|err|={err:.3e}")
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol,
                                   err_msg=f"{name} output {i}")


def spd_stack(rng, B, D):
    A = rng.standard_normal((B, D, D)).astype(np.float32)
    return (A @ A.transpose(0, 2, 1) / D
            + np.eye(D, dtype=np.float32)).astype(np.float32)


def phase_kernels(seed: int):
    from repro.core.objectives import get_objective
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    N, D, B = N_PARTICLES, 10, 1024
    for name in ("rastrigin", "ackley"):
        obj = get_objective(name)
        x = jnp.asarray(rng.uniform(obj.lower, obj.upper, (N, D)),
                        jnp.float32)
        vg_ref = getattr(ref, f"{name}_vg_ref")
        assert_close(f"fused_value {name}",
                     compiled_call(lambda x: ops.fused_value(name, x), x),
                     oracle(lambda x: vg_ref(x)[0], x), 1e-5, 1e-3)
        assert_close(f"fused_value_grad {name}",
                     compiled_call(lambda x: ops.fused_value_grad(name, x), x),
                     oracle(vg_ref, x), 1e-5, 1e-3)

    H = jnp.asarray(spd_stack(rng, B, D))
    g = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)
    assert_close("direction", compiled_call(ops.direction, H, g),
                 oracle(ref.direction_ref, H, g), 1e-4, 1e-4)

    # the engine's guard: rho = 1/(dx·dg) where the curvature is positive
    # and the lane active, else rho = 0 with zeroed (dx, dg)
    dx = rng.standard_normal((B, D)).astype(np.float32)
    dg = dx + 0.3 * rng.standard_normal((B, D)).astype(np.float32)
    curv = np.sum(dx * dg, axis=1)
    ok = (curv > 1e-10) & (rng.random(B) > 0.1)
    rho = np.where(ok, 1.0 / np.where(ok, curv, 1.0), 0.0).astype(np.float32)
    dx, dg = np.where(ok[:, None], dx, 0.0), np.where(ok[:, None], dg, 0.0)
    args = (H, jnp.asarray(dx, jnp.float32), jnp.asarray(dg, jnp.float32),
            g, jnp.asarray(rho))
    assert_close("guarded_update_direction",
                 compiled_call(ops.guarded_update_direction, *args),
                 oracle(ref.guarded_update_direction_ref, *args), 1e-4, 1e-4)

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x, v, px = (f32(rng.uniform(-5, 5, (N, D))) for _ in range(3))
    gx = f32(rng.uniform(-5, 5, (D,)))
    r1, r2 = (f32(rng.random((N, D))) for _ in range(2))
    assert_close("pso_step",
                 compiled_call(lambda *a: ops.pso_step_update(
                     *a, 0.5, 1.2, 1.5), x, v, px, gx, r1, r2),
                 oracle(lambda *a: ref.pso_step_ref(*a, 0.5, 1.2, 1.5),
                        x, v, px, gx, r1, r2), 1e-5, 1e-4)

    xi = f32(rng.standard_normal((N, D)))
    for noise in ("isotropic", "anisotropic"):
        assert_close(f"meanfield_step {noise}",
                     compiled_call(lambda *a: ops.meanfield_step_update(
                         *a, 0.5, 0.3, 0.1, noise), x, v, gx, xi),
                     oracle(lambda *a: ref.meanfield_step_ref(
                         *a, 0.5, 0.3, 0.1, noise), x, v, gx, xi),
                     1e-5, 1e-4)


# -- phases 3, 4, 6: solves ---------------------------------------------------
def rastrigin_vg64(x):
    """float64 Rastrigin value and gradient, independent of the program."""
    x = np.asarray(x, np.float64)
    f = 10.0 * x.size + np.sum(x * x - 10.0 * np.cos(2 * np.pi * x))
    return f, 2.0 * x + 20.0 * np.pi * np.sin(2 * np.pi * x)


def zeus_options(mode: str):
    from repro.core import BFGSOptions, PSOOptions, ZeusOptions

    return ZeusOptions(
        pso=PSOOptions(n_particles=N_PARTICLES, iter_pso=ITER_PSO,
                       use_kernel=True),
        bfgs=BFGSOptions(iter_bfgs=ITER_BFGS, theta=THETA),
        sweep_mode=mode, lane_chunk=LANE_CHUNK)


def summarize(tag, res, dim):
    """Check one ZeusResult and return its summary numbers."""
    from repro.core import CONVERGED

    x = np.asarray(res.raw.x)
    status = np.asarray(res.raw.status)
    conv = status == CONVERGED
    if x.shape != (N_PARTICLES, dim):
        raise AssertionError(f"{tag}: lanes {x.shape} != {(N_PARTICLES, dim)}")
    if not conv.any():
        raise AssertionError(f"{tag}: no lane converged")
    if not np.isfinite(x[conv]).all():
        raise AssertionError(f"{tag}: non-finite converged iterate")
    best_x, best_f = np.asarray(res.best_x), float(res.best_f)
    n_correct = int(np.sum((np.linalg.norm(x, axis=1) < BASIN_TOL) & conv))
    # the reported best is a local minimizer of the objective, by a
    # float64 evaluation that shares no code with the program
    f64, g64 = rastrigin_vg64(best_x)
    if abs(f64 - best_f) > 1e-3 or np.linalg.norm(g64) > 1e-2:
        raise AssertionError(
            f"{tag}: best_f={best_f} but f64(best_x)={f64}, "
            f"|grad64|={np.linalg.norm(g64):.3e}")
    out = {"n_converged": int(res.n_converged), "n_correct": n_correct,
           "best_f": best_f, "best_x_norm": float(np.linalg.norm(best_x)),
           "x": x}
    log(f"[{tag}] n_converged={out['n_converged']} n_correct={n_correct} "
        f"best_f={best_f:.6e} |best_x|={out['best_x_norm']:.4f} "
        f"|grad64(best_x)|={np.linalg.norm(g64):.3e}")
    if dim == BASIN_DIM and not (n_correct > 0
                                 and out["best_x_norm"] < BASIN_TOL):
        raise AssertionError(f"{tag}: global basin not found")
    return out


def timed_solve(tag, run, key, warm: bool):
    t0 = time.perf_counter()
    exe = run.lower(key).compile()
    t_compile = time.perf_counter() - t0
    require_kernel(exe, tag)
    t0 = time.perf_counter()
    res = jax.block_until_ready(exe(key))
    t_first = time.perf_counter() - t0
    msg = f"[{tag}] compile={t_compile:.2f}s first_call={t_first:.3f}s"
    if warm:
        t0 = time.perf_counter()
        res = jax.block_until_ready(exe(key))
        msg += f" warm_call={time.perf_counter() - t0:.3f}s"
    log(msg + " (smoke timings)")
    return res


def zeus_solve(mode, dim, seed):
    from repro.core.objectives import get_objective
    from repro.core.zeus import zeus_jit

    obj = get_objective("rastrigin")
    tag = f"{mode} D={dim}"
    run = zeus_jit(obj.fn, dim, obj.lower, obj.upper, zeus_options(mode))
    res = timed_solve(tag, run, jax.random.key(seed), warm=dim == DIMS[0])
    return summarize(tag, res, dim)


def phase_batched(seed, solved):
    for dim in DIMS:
        solved[dim] = zeus_solve("batched", dim, seed)


def phase_megakernel(seed, solved):
    for dim in DIMS:
        with warnings.catch_warnings():
            # a fallback to the staged path warns at trace time
            warnings.simplefilter("error", RuntimeWarning)
            mk = zeus_solve("megakernel", dim, seed)
        if dim not in solved:
            raise AssertionError(f"no batched D={dim} solve to compare with")
        b = solved[dim]
        d_conv = abs(mk["n_converged"] - b["n_converged"])
        d_f = abs(mk["best_f"] - b["best_f"])
        same = np.array_equal(mk["x"], b["x"])
        log(f"[megakernel D={dim}] vs batched: |d n_converged|={d_conv} "
            f"|d best_f|={d_f:.3e} lanes array-equal={same}")
        # stated tolerance: 1% of the lanes; best f within one Rastrigin
        # lattice step (~0.995) at D=10, where a rounding fork can move a
        # lane between the few lowest shells, and 1e-3 at the global basin
        f_tol = 1.0 if dim == DIMS[0] else 1e-3
        if d_conv > 0.01 * N_PARTICLES or d_f > f_tol:
            raise AssertionError(
                f"megakernel D={dim} disagrees with batched beyond "
                f"(1% lanes, {f_tol}) tolerance")


def phase_service(seed):
    from repro.core import CONVERGED, DIVERGED, BFGSOptions, ZeusOptions
    from repro.serve.service import ProblemRegistry, SolveRequest, SolveService

    opts = ZeusOptions(bfgs=BFGSOptions(iter_bfgs=ITER_BFGS, theta=THETA,
                                        ad_mode="reverse",
                                        sweep_mode="batched"))
    registry = ProblemRegistry()
    names = ("rastrigin:10", "ackley:10")
    for pname in names:
        obj_name, dim = pname.split(":")
        registry.register(pname, obj_name, int(dim), opts=opts)
    service = SolveService(registry, slots=SERVICE_SLOTS, max_queue=64)
    t0 = time.perf_counter()
    rids = [service.submit(SolveRequest(names[i % 2], seed=seed + i,
                                        n_starts=SERVICE_STARTS))
            for i in range(SERVICE_REQUESTS)]
    results = service.drain()
    wall = time.perf_counter() - t0
    for rid in rids:
        r = results[rid]
        log(f"[service] rid={rid} {r.problem} status={r.status} "
            f"conv={r.n_converged}/{len(r.lanes)} best_f={r.best_f:.6e}")
        if r.status not in (CONVERGED, DIVERGED) or not np.isfinite(r.best_f):
            raise AssertionError(f"request {rid} did not retire cleanly")
    if sorted(results) != sorted(rids):
        raise AssertionError("not every request was answered")
    st = service.stats()
    log(f"[service] {len(results)} requests in {wall:.2f}s incl. compile "
        f"(smoke timing); sweeps/pool={st['pool_sweeps']}")


def phase_mesh(seed):
    from repro.core.distributed import distributed_zeus
    from repro.core.objectives import get_objective
    from repro.sharding import make_mesh

    dim = BASIN_DIM
    obj = get_objective("rastrigin")
    mesh = make_mesh((4,), ("lanes",))
    run = jax.jit(distributed_zeus(obj.fn, dim, obj.lower, obj.upper,
                                   zeus_options("batched"), mesh))
    res = timed_solve(f"mesh4 D={dim}", run, jax.random.key(seed), warm=True)
    devices = {s.device for s in res.raw.x.addressable_shards}
    log(f"[mesh4 D={dim}] lane shards on {len(devices)} devices: "
        f"{sorted(d.id for d in devices)}")
    if len(devices) != 4:
        raise AssertionError("lanes are not spread over four devices")
    summarize(f"mesh4 D={dim}", res, dim)
    zeus_solve("batched", dim, seed)  # the one-chip solve it is compared with


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = device_check(args.chips)
    xla_flags = os.environ.get("XLA_FLAGS")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro import compile_cache

    log(f"[setup] compile cache: {compile_cache.enable()}")

    solved = {}
    if args.chips == 4:
        phases = [("mesh", lambda: phase_mesh(args.seed))]
    else:
        phases = [
            ("kernels", lambda: phase_kernels(args.seed)),
            ("batched", lambda: phase_batched(args.seed, solved)),
            ("megakernel", lambda: phase_megakernel(args.seed, solved)),
            ("service", lambda: phase_service(args.seed)),
        ]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s")
        else:
            log(f"[{name}] passed in {time.perf_counter() - t0:.1f}s")
    if os.environ.get("XLA_FLAGS") != xla_flags:
        failed.append("XLA_FLAGS changed by an import")
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
