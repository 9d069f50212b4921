"""Sharding rules + distributed ZEUS + dry-run machinery.

Multi-device tests run in a subprocess because
xla_force_host_platform_device_count must be set before jax initializes
(the main pytest process intentionally sees ONE device)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.sharding import (DEFAULT_RULES, logical_to_spec, make_mesh,
                            resolve_axis)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


class TestShardingRules:
    def _mesh(self):
        return make_mesh((1,), ("data",))

    def test_divisibility_fallback(self):
        mesh = self._mesh()
        # axis size 1 -> never sharded
        assert resolve_axis(mesh, "heads", 8) is None

    def test_spec_no_duplicate_mesh_axes(self):
        import jax as _j
        mesh = make_mesh((1, 1), ("data", "model"))
        spec = logical_to_spec(mesh, ("expert", "fsdp", "expert_mlp"),
                               (8, 64, 64))
        flat = []
        for part in spec:
            if part is None:
                continue
            flat.extend(part if isinstance(part, tuple) else (part,))
        assert len(flat) == len(set(flat))


def test_multi_device_sharding_resolution():
    out = run_subprocess("""
        import jax
        from repro.sharding import logical_to_spec, make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        # kv_heads=2 does not divide model=4 -> replicated
        spec = logical_to_spec(mesh, ("fsdp", "kv_heads", "head_dim"), (64, 2, 16))
        assert spec[1] is None, spec
        assert spec[0] == "data", spec
        # heads=8 divides model=4 -> sharded
        spec = logical_to_spec(mesh, ("fsdp", "heads", "head_dim"), (64, 8, 16))
        assert spec[1] == "model", spec
        print("OK")
    """)
    assert "OK" in out


def test_distributed_zeus_multidevice():
    """Full distributed ZEUS on 8 emulated devices: finds sphere optimum,
    global best identical on every device, lanes sharded over the mesh."""
    out = run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.core import BFGSOptions, PSOOptions, ZeusOptions
        from repro.core.distributed import distributed_zeus
        from repro.core.objectives import sphere
        from repro.sharding import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        opts = ZeusOptions(pso=PSOOptions(n_particles=128, iter_pso=4),
                           bfgs=BFGSOptions(iter_bfgs=60, theta=1e-4,
                                            required_c=64))
        run = jax.jit(distributed_zeus(sphere, 3, -5.0, 5.0, opts, mesh))
        res = run(jax.random.key(0))
        assert float(res.best_f) < 1e-5, float(res.best_f)
        assert int(res.n_converged) >= 64
        # lanes live sharded across every mesh axis
        assert res.raw.x.sharding.spec == jax.sharding.PartitionSpec(("data", "model"),)
        print("OK", float(res.best_f), int(res.n_converged))
    """)
    assert "OK" in out


def test_meanfield_moments_shard_count_invariant():
    """ISSUE 10: the mean-field consensus psum'd through make_pmoments is
    shard-count invariant — the SAME global particle set reduced on 1, 2,
    4 and 8 shards yields the same consensus point (tolerance-level: the
    log-sum-exp re-shift exp(m−M) and the psum order differ per layout,
    so bitwise equality is not expected). Also runs distributed ZEUS with
    phase1="meanfield" end to end on the 8-device mesh."""
    out = run_subprocess("""
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.distributed import make_pmoments
        from repro.core.meanfield import consensus_point
        from repro.core.objectives import rastrigin
        from repro.sharding import make_mesh

        x = jax.random.uniform(jax.random.key(1), (64, 5),
                               minval=-5.12, maxval=5.12)
        fv = jax.vmap(rastrigin)(x)
        want = consensus_point(fv, x, 30.0)  # single-host reduction
        for n_shards in (1, 2, 4, 8):
            mesh = make_mesh((n_shards,), ("d",))
            fn = jax.shard_map(
                lambda fv, x: consensus_point(fv, x, 30.0,
                                              make_pmoments(("d",))),
                mesh=mesh, in_specs=(P("d"), P("d")), out_specs=P(),
                check_vma=False)
            got = jax.jit(fn)(fv, x)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6, atol=1e-6)

        # end to end: phase1="meanfield" through the sharded driver
        from repro.core import (BFGSOptions, MeanFieldPSOOptions,
                                ZeusOptions)
        from repro.core.distributed import distributed_zeus
        from repro.core.objectives import sphere
        mesh = make_mesh((2, 4), ("data", "model"))
        opts = ZeusOptions(
            phase1="meanfield",
            meanfield=MeanFieldPSOOptions(n_particles=128, iter_pso=4),
            bfgs=BFGSOptions(iter_bfgs=60, theta=1e-4, required_c=64))
        res = jax.jit(distributed_zeus(sphere, 3, -5.0, 5.0, opts,
                                       mesh))(jax.random.key(0))
        assert float(res.best_f) < 1e-5, float(res.best_f)
        assert res.raw.x.shape == (128, 3)
        assert jnp.isfinite(res.pso_best_f)
        print("OK", float(res.best_f))
    """)
    assert "OK" in out


def test_distributed_repack_and_ladder():
    """ISSUE 4: the batched sweep's global lane repacking and adaptive
    ladder compose with distributed_zeus — each shard repacks its own
    lanes, and the eval_rows/map_trips diagnostics are psum'd across the
    mesh (replicated scalars, smaller than the static schedule's)."""
    out = run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.core import BFGSOptions, PSOOptions, ZeusOptions
        from repro.core.distributed import distributed_zeus
        from repro.core.objectives import rosenbrock
        from repro.sharding import make_mesh
        mesh = make_mesh((4,), ("data",))
        # rosenbrock over its full range: lanes converge at widely
        # different sweeps, so the tail the repacker compresses actually
        # exists on every shard. required_c must be the GLOBAL lane count:
        # the psum'd stop protocol counts convergences across the mesh,
        # and the per-device default (local B) would stop the solve long
        # before the tail regime.
        base = dict(use_pso=False,
                    pso=PSOOptions(n_particles=128, iter_pso=0),
                    bfgs=BFGSOptions(iter_bfgs=100, theta=1e-4,
                                     required_c=128),
                    sweep_mode="batched", lane_chunk=4)
        key = jax.random.key(3)
        ref = jax.jit(distributed_zeus(
            rosenbrock, 2, -5.0, 10.0, ZeusOptions(**base), mesh))(key)
        rep = jax.jit(distributed_zeus(
            rosenbrock, 2, -5.0, 10.0,
            ZeusOptions(repack_every=1, ladder_len=2, **base), mesh))(key)
        import numpy as np
        np.testing.assert_array_equal(np.asarray(ref.raw.status),
                                      np.asarray(rep.raw.status))
        np.testing.assert_array_equal(np.asarray(ref.best_x),
                                      np.asarray(rep.best_x))
        assert int(ref.raw.iterations) == int(rep.raw.iterations)
        # psum'd whole-mesh diagnostics: the repacked tail does less work
        assert int(rep.raw.map_trips) < int(ref.raw.map_trips)
        assert int(rep.raw.eval_rows) < int(ref.raw.eval_rows)
        print("OK", int(ref.raw.map_trips), int(rep.raw.map_trips))
    """, devices=4)
    assert "OK" in out


def test_distributed_auto_schedule():
    """ISSUE 5: schedule="auto" composes with distributed_zeus — each shard
    runs its own controller on its own (collective-free) signals, the
    trajectory stays array-equal to the static schedule, and the
    ScheduleTrace is psum'd: row w of the replicated trace counts how many
    shards ran plan p in window w, so every executed window sums to the
    shard count."""
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import BFGSOptions, PSOOptions, ZeusOptions
        from repro.core.distributed import distributed_zeus
        from repro.core.objectives import rosenbrock
        from repro.sharding import make_mesh
        mesh = make_mesh((4,), ("data",))
        base = dict(use_pso=False,
                    pso=PSOOptions(n_particles=64, iter_pso=0),
                    bfgs=BFGSOptions(iter_bfgs=60, theta=1e-4, ls_iters=10,
                                     required_c=64, auto_ladders=(2, 0)),
                    sweep_mode="batched", lane_chunk=4)
        key = jax.random.key(3)
        ref = jax.jit(distributed_zeus(
            rosenbrock, 2, -5.0, 10.0, ZeusOptions(**base), mesh))(key)
        aut = jax.jit(distributed_zeus(
            rosenbrock, 2, -5.0, 10.0,
            ZeusOptions(schedule="auto", schedule_every=2, **base),
            mesh))(key)
        assert ref.raw.schedule_trace is None
        np.testing.assert_array_equal(np.asarray(ref.raw.status),
                                      np.asarray(aut.raw.status))
        np.testing.assert_array_equal(np.asarray(ref.best_x),
                                      np.asarray(aut.best_x))
        assert int(ref.raw.iterations) == int(aut.raw.iterations)
        tr = np.asarray(aut.raw.schedule_trace)
        # sweeps are globally synchronized, so every shard logged one plan
        # per executed window: psum'd rows sum to the shard count
        executed = -(-int(aut.raw.iterations) // 2)
        sums = tr.sum(axis=1)
        assert (sums[:executed] == 4).all(), tr
        assert (sums[executed:] == 0).all(), tr
        print("OK", int(aut.raw.iterations), tr.sum())
    """, devices=4)
    assert "OK" in out


def test_distributed_equals_single_device_semantics():
    """required_c semantics hold globally: stop counts converged lanes
    across all devices, not per device."""
    out = run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.core import BFGSOptions, PSOOptions, ZeusOptions, STOPPED
        from repro.core.distributed import distributed_zeus
        from repro.core.objectives import sphere
        from repro.sharding import make_mesh
        mesh = make_mesh((8,), ("data",))
        opts = ZeusOptions(use_pso=False,
                           pso=PSOOptions(n_particles=64, iter_pso=0),
                           bfgs=BFGSOptions(iter_bfgs=100, theta=1e-12,
                                            required_c=8))
        run = jax.jit(distributed_zeus(sphere, 2, -5.0, 5.0, opts, mesh))
        res = run(jax.random.key(1))
        # theta=1e-12 in f32: few lanes converge exactly; stop must still
        # trigger via the GLOBAL count or budget exhaustion without hanging
        assert int(res.raw.iterations) <= 100
        print("OK")
    """)
    assert "OK" in out


def test_dryrun_single_cell_subprocess():
    """The dry-run machinery end to end on one small arch × mesh."""
    out = run_subprocess("""
        from repro.launch.dryrun import analyze_cell
        r = analyze_cell("xlstm-125m", "decode_32k", "single")
        assert r["status"] == "ok"
        t = r["terms"]
        assert t["flops"] > 0 and t["memory_s"] > 0
        assert r["per_device_peak_bytes"] < 16 * 2**30  # fits one v5e
        print("OK", t["bottleneck"])
    """, devices=512)
    assert "OK" in out


def test_hlo_analysis_known_programs():
    from repro.launch.hlo_analysis import analyze_hlo
    import jax.numpy as jnp

    def scanned(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    comp = jax.jit(scanned).lower(x, x).compile()
    r = analyze_hlo(comp.as_text(), 1)
    expect = 7 * 2 * 128**3
    assert abs(r["flops"] - expect) / expect < 0.02, r["flops"]


def test_roofline_term_math():
    from repro.launch.roofline import derive_terms, PEAK_FLOPS, HBM_BW, ICI_BW
    terms = derive_terms(
        flops=PEAK_FLOPS,        # exactly 1 second of compute
        hbm_bytes=HBM_BW * 0.5,  # 0.5 s of memory
        collectives={"all-reduce": {"wire_bytes": ICI_BW * 2.0, "count": 1,
                                    "payload_bytes": 0}},
        model_flops_global=PEAK_FLOPS * 0.5,
        n_devices=1,
    )
    assert terms.compute_s == pytest.approx(1.0)
    assert terms.memory_s == pytest.approx(0.5)
    assert terms.collective_s == pytest.approx(2.0)
    assert terms.bottleneck == "collective"
    assert terms.useful_flop_ratio == pytest.approx(0.5)


def test_gradient_compression_cross_pod_psum():
    """Error-feedback int8 compression through a REAL psum over a pod axis
    (shard_map on 8 emulated devices): the reduced gradient matches the
    uncompressed psum within quantization error, and error feedback
    converges a data-parallel quadratic."""
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.train.compress import (CompressionConfig,
                                          compress_and_reduce,
                                          init_error_state)
        from repro.sharding import make_mesh
        mesh = make_mesh((8,), ("pod",))
        ccfg = CompressionConfig(kind="int8")

        def shard_step(g_local, e_local):
            psum = lambda x: jax.lax.psum(x, "pod")
            pmax = lambda x: jax.lax.pmax(x, "pod")
            red, e = compress_and_reduce(ccfg, {"w": g_local}, {"w": e_local},
                                          psum, pmax)
            return red["w"], e["w"]

        f = jax.jit(jax.shard_map(shard_step, mesh=mesh,
                                  in_specs=(P("pod"), P("pod")),
                                  out_specs=(P("pod"), P("pod")),
                                  check_vma=False))
        # per-pod gradient shards (B=8 pods, each holds a (1, 64) slice)
        g = jax.random.normal(jax.random.key(0), (8, 64)) * 1e-2
        e0 = jnp.zeros((8, 64))
        red, e1 = f(g, e0)
        # every pod sees the same reduced value = sum over pods
        expect = jnp.sum(g, axis=0)
        got = red[0]
        err = float(jnp.max(jnp.abs(got - expect)))
        scale = float(jnp.max(jnp.abs(g))) / 127 * 8
        assert err <= scale + 1e-6, (err, scale)
        # error feedback captured the per-pod residuals
        assert float(jnp.max(jnp.abs(e1))) <= float(jnp.max(jnp.abs(g))) / 127 + 1e-6
        print("OK", err)
    """)
    assert "OK" in out
