"""Distributed ZEUS across a device mesh (the paper's Alg. 7 at pod scale).

    PYTHONPATH=src python examples/distributed_zeus.py

Runs the sharded swarm on every device this host has (the same shard_map
program scales to the (pod, data, model) production mesh — see
core/distributed.py). Set XLA_FLAGS=--xla_force_host_platform_device_count=8
to emulate 8 devices on CPU.
"""
import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import (
    BFGSOptions,
    MeanFieldPSOOptions,
    PSOOptions,
    ZeusOptions,
)
from repro.core.distributed import distributed_zeus
from repro.core.objectives import get_objective
from repro.launch.mesh import make_host_mesh

DIM = 5


def main():
    obj = get_objective("rastrigin")
    mesh = make_host_mesh(model_parallel=2)
    n_dev = len(jax.devices())
    # 5-D Rastrigin has 11^5 local minima: basin capture is stochastic in
    # the swarm size (the paper's Fig. 1). 512 particles/device with a
    # dozen PSO sweeps gives a comfortable hit rate.
    opts = ZeusOptions(
        pso=PSOOptions(n_particles=512 * n_dev, iter_pso=12),
        bfgs=BFGSOptions(iter_bfgs=100, theta=1e-4, required_c=128 * n_dev),
    )
    run = jax.jit(distributed_zeus(obj.fn, DIM, obj.lower, obj.upper, opts, mesh))
    res = run(jax.random.key(0))

    err = float(jnp.linalg.norm(res.best_x - obj.x_star(DIM)))
    print(f"mesh          : {dict(mesh.shape)} ({n_dev} devices)")
    print(f"swarm         : {opts.pso.n_particles} particles "
          f"({opts.pso.n_particles // n_dev}/device)")
    print(f"best f        : {float(res.best_f):.3e}   err {err:.3e}")
    print(f"converged     : {int(res.n_converged)} lanes")
    print(f"lane sharding : {res.raw.x.sharding.spec}")
    assert err < 0.5
    print("OK — distributed swarm found the global basin")

    # Same mesh, mean-field phase 1 (DESIGN.md §18): each shard evolves its
    # local particles against the GLOBAL consensus point, reduced with two
    # O(D) psums per iteration — the strategy whose per-device collective
    # traffic stays constant as the swarm grows to 10^6+ particles.
    # fewer sweeps than the paper swarm: consensus dynamics contract the
    # cloud every iteration, and the start set should still be spread over
    # the low basins when phase 2 takes over (DESIGN.md §18)
    mf_opts = ZeusOptions(
        phase1="meanfield",
        meanfield=MeanFieldPSOOptions(n_particles=512 * n_dev, iter_pso=6,
                                      beta=30.0),
        bfgs=BFGSOptions(iter_bfgs=100, theta=1e-4, required_c=128 * n_dev),
    )
    mf_run = jax.jit(
        distributed_zeus(obj.fn, DIM, obj.lower, obj.upper, mf_opts, mesh))
    mf_res = mf_run(jax.random.key(0))
    mf_err = float(jnp.linalg.norm(mf_res.best_x - obj.x_star(DIM)))
    print(f"meanfield f   : {float(mf_res.best_f):.3e}   err {mf_err:.3e}")
    # at this swarm size the consensus start set lands phase 2 in the
    # lowest shell of basins (see examples/quickstart.py for the caveat;
    # the coverage-per-row criterion is gated in benchmarks/engine_bench)
    assert float(mf_res.best_f) < 3.0
    print("OK — distributed mean-field starts landed in the lowest shell")


if __name__ == "__main__":
    compile_cache.enable()
    main()
