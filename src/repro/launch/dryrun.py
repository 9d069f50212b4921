import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell we jit the right step function (train_step / prefill_step /
serve_step) with in_shardings derived from the logical-axis rules, lower it
against ShapeDtypeStruct stand-ins (no allocation anywhere), compile the
SPMD partitioned module, and record:
    memory_analysis()  — proves the per-device working set fits HBM,
    cost_analysis()    — per-device FLOPs/bytes for the roofline,
    collective schedule — parsed from the partitioned HLO text.

Results append incrementally to a JSON file so a long sweep resumes.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch all --shape all --mesh single,multi --out dryrun_results.json
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh, mesh_device_count
from repro.models.registry import Model, build_model
from repro.serve.decode import DecodeState, make_prefill_step, make_serve_step
from repro.sharding import named_sharding
from repro.train.optimizer import OptState
from repro.train.step import TrainConfig, TrainState, make_train_step

# long_500k requires sub-quadratic attention; run only where that holds
# (SSM / hybrid / half-sliding-window stacks). See DESIGN.md §5.
LONG_CONTEXT_ARCHS = {"zamba2-1.2b", "xlstm-125m", "gemma2-2b"}


def cell_supported(arch: str, shape: ShapeConfig) -> Optional[str]:
    if shape.name == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
        return ("pure full-attention stack: 500k context intentionally "
                "skipped (DESIGN.md §5)")
    return None


def _replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P())  # scalar/replicated spec


def lower_cell(arch: str, shape_name: str, mesh, *, tcfg: Optional[TrainConfig] = None):
    """Returns (lowered, compiled, model, meta) for one cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    # dry-run defaults chosen to fit a 256-chip v5e pod at 235B/314B scale:
    # remat + microbatching (saved residuals ~B/mb per layer), bf16 params,
    # bf16 optimizer moments (see OptimizerConfig.moment_dtype note).
    # mb is capped so each microbatch still divides the data-parallel ways
    # (mb=16 on a 32-way multi-pod mesh would leave 16 rows for 32 shards).
    from repro.train.optimizer import OptimizerConfig
    if tcfg is None:
        dp_ways = 1
        for ax in ("pod", "data"):
            if ax in mesh.axis_names:
                dp_ways *= mesh.shape[ax]
        mb = max(1, min(16, shape.global_batch // dp_ways))
        tcfg = TrainConfig(
            optimizer=OptimizerConfig(moment_dtype="bfloat16"),
            remat=True, microbatches=mb, param_dtype="bfloat16",
        )

    p_shard = model.param_shardings(mesh)
    p_abs = model.abstract_params(
        jnp.bfloat16 if tcfg.param_dtype == "bfloat16" else jnp.float32)

    if shape.kind == "train":
        step = make_train_step(model, tcfg, mesh)
        opt_shard = OptState(step=_replicated(mesh), mu=p_shard, nu=p_shard)
        state_shard = TrainState(params=p_shard, opt=opt_shard,
                                 step=_replicated(mesh))
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        mdt = jnp.dtype(tcfg.optimizer.moment_dtype)
        mlike = lambda t: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, mdt), t)
        state_abs = TrainState(
            params=p_abs,
            opt=OptState(step=scalar, mu=mlike(p_abs), nu=mlike(p_abs)),
            step=scalar,
        )
        batch_abs = model.input_specs(shape)
        batch_shard = model.input_shardings(mesh, shape)
        jitted = jax.jit(step, in_shardings=(state_shard, batch_shard),
                         donate_argnums=(0,))
        lowered = jitted.lower(state_abs, batch_abs)

    elif shape.kind == "prefill":
        step = make_prefill_step(model, mesh)
        batch_abs = model.input_specs(shape)
        batch_shard = model.input_shardings(mesh, shape)
        jitted = jax.jit(step, in_shardings=(p_shard, batch_shard))
        lowered = jitted.lower(p_abs, batch_abs)

    else:  # decode
        step = make_serve_step(model, mesh)
        B, S = shape.global_batch, shape.seq_len
        cache_abs = model.cache_specs(B, S, jnp.bfloat16)
        cache_shard = model.cache_shardings(mesh, B, S, jnp.bfloat16)
        tok_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        tok_shard = named_sharding(mesh, ("batch", None), (B, 1))
        key_abs = jax.eval_shape(lambda: jax.random.key(0))
        state_abs = DecodeState(cache=cache_abs,
                                pos=jax.ShapeDtypeStruct((), jnp.int32),
                                last_tokens=tok_abs, key=key_abs)
        state_shard = DecodeState(cache=cache_shard, pos=_replicated(mesh),
                                  last_tokens=tok_shard, key=_replicated(mesh))
        jitted = jax.jit(step, in_shardings=(p_shard, state_shard),
                         donate_argnums=(1,))
        lowered = jitted.lower(p_abs, state_abs)

    compiled = lowered.compile()
    return lowered, compiled, model, {"cfg": cfg, "shape": shape}


def analyze_cell(arch: str, shape_name: str, mesh_kind: str,
                 tcfg: Optional[TrainConfig] = None) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh_device_count(mesh)
    shape = SHAPES[shape_name]
    t0 = time.perf_counter()  # monotonic: NTP steps can't corrupt compile_s
    from repro.models.registry import build_model as _bm
    from repro.configs import get_config as _gc
    with _bm(_gc(arch)).rules_context():
        with mesh:
            lowered, compiled, model, meta = lower_cell(arch, shape_name, mesh,
                                                        tcfg=tcfg)
    compile_s = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # trip-count-aware analysis of the partitioned module (XLA's own
    # cost_analysis counts while bodies once — see hlo_analysis.py)
    from repro.launch.hlo_analysis import analyze_hlo
    ha = analyze_hlo(hlo, n_dev)
    colls = ha["collectives"]

    cfg = meta["cfg"]
    n_active = cfg.n_active_params()
    mf = RL.model_flops_global(cfg, shape, n_active)
    # memory term from major-op (dot/gather/collective) boundary bytes — the
    # post-fusion HBM streams a TPU backend issues; the every-op count is
    # recorded as an unfused upper bound (see hlo_analysis.Cost)
    terms = RL.derive_terms(float(ha["flops"]), float(ha["major_bytes"]),
                            colls, mf, n_dev)
    xla_reported = {
        "flops_body_once": float(cost.get("flops", 0.0)),
        "bytes_body_once": float(cost.get("bytes accessed", 0.0)),
        "bytes_unfused_upper_bound": float(ha["bytes"]),
    }
    mem_info = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
    }
    peak = sum(v for v in (mem_info["argument_bytes"], mem_info["temp_bytes"])
               if v is not None)
    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "n_devices": n_dev,
        "status": "ok",
        "compile_s": round(compile_s, 1),
        "memory": mem_info,
        "per_device_peak_bytes": peak,
        "terms": terms.as_dict(),
        "collectives": colls,
        "xla_reported": xla_reported,
        "n_params": model.n_params(),
        "n_active_params": n_active,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = args.mesh.split(",")

    results = {}
    if os.path.exists(args.out) and not args.force:
        with open(args.out) as f:
            results = json.load(f)

    def save():
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)

    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                cell = f"{arch}|{shape_name}|{mesh_kind}"
                if cell in results and results[cell].get("status") in ("ok", "skip"):
                    continue
                skip = cell_supported(arch, SHAPES[shape_name])
                if skip:
                    results[cell] = {"arch": arch, "shape": shape_name,
                                     "mesh": mesh_kind, "status": "skip",
                                     "reason": skip}
                    save()
                    print(f"[skip] {cell}: {skip}", flush=True)
                    continue
                print(f"[lower+compile] {cell} ...", flush=True)
                try:
                    results[cell] = analyze_cell(arch, shape_name, mesh_kind)
                    t = results[cell]["terms"]
                    print(
                        f"  ok ({results[cell]['compile_s']}s) "
                        f"bottleneck={t['bottleneck']} "
                        f"compute={t['compute_s']:.3e}s "
                        f"memory={t['memory_s']:.3e}s "
                        f"coll={t['collective_s']:.3e}s "
                        f"peak/dev={results[cell]['per_device_peak_bytes']/2**30:.2f}GiB",
                        flush=True,
                    )
                except Exception as e:
                    results[cell] = {"arch": arch, "shape": shape_name,
                                     "mesh": mesh_kind, "status": "error",
                                     "error": f"{type(e).__name__}: {e}",
                                     "trace": traceback.format_exc()[-2000:]}
                    print(f"  ERROR {type(e).__name__}: {e}", flush=True)
                save()

    n_ok = sum(1 for r in results.values() if r.get("status") == "ok")
    n_skip = sum(1 for r in results.values() if r.get("status") == "skip")
    n_err = sum(1 for r in results.values() if r.get("status") == "error")
    print(f"done: {n_ok} ok, {n_skip} documented skips, {n_err} errors")


if __name__ == "__main__":
    main()
