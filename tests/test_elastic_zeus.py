"""Elastic fault tolerance for the paper's optimizer: checkpoint a swarm
mid-optimization, 'lose' a slice of lanes, re-seed, resume — the
launch/faults.py + checkpoint/manager.py story end to end."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import manager as ckpt
from repro.core import BFGSOptions, PSOOptions, batched_bfgs
from repro.core.objectives import get_objective
from repro.core.pso import run_pso
from repro.launch.faults import reseed_lost_lanes
from repro.sharding import make_mesh

KEY = jax.random.key(7)


def test_swarm_checkpoint_lose_reseed_resume(tmp_path):
    obj = get_objective("rastrigin")
    dim, n = 2, 128

    # phase 1 on "cluster A": PSO then checkpoint the swarm
    swarm = run_pso(obj.fn, KEY, dim, obj.lower, obj.upper,
                    PSOOptions(n_particles=n, iter_pso=6))
    ckpt.save(str(tmp_path), step=1, tree={"x": swarm.x})

    # restart: restore, simulate losing the lanes of 2 of 8 'hosts'
    restored = ckpt.restore(str(tmp_path), {"x": swarm.x})
    lost = jnp.arange(n) < n // 4
    x0 = reseed_lost_lanes(jax.random.key(99), restored["x"], lost,
                           obj.lower, obj.upper)
    # surviving lanes are bit-identical to the checkpoint
    np.testing.assert_array_equal(np.asarray(x0[n // 4:]),
                                  np.asarray(swarm.x[n // 4:]))

    # phase 2 resumes at full strength and still solves the problem
    res = batched_bfgs(obj.fn, x0,
                       BFGSOptions(iter_bfgs=80, theta=1e-4, required_c=40))
    assert int(res.n_converged) >= 40
    best = float(jnp.min(jnp.where(res.status == 1, res.fval, jnp.inf)))
    assert best < 2.0  # in or adjacent to the global basin


def test_trainstate_cross_mesh_restore_values(tmp_path):
    """Elastic restart of the LM trainer: values survive a re-shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config, reduce_config
    from repro.models import build_model
    from repro.train.step import TrainConfig, init_train_state

    cfg = reduce_config(get_config("xlstm-125m"))
    model = build_model(cfg)
    state = init_train_state(model, KEY, TrainConfig())
    ckpt.save(str(tmp_path), step=3, tree=state)

    mesh = make_mesh((1,), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
    out = ckpt.restore(str(tmp_path), state, shardings=sh)
    a = jax.tree.leaves(state.params)[0]
    b = jax.tree.leaves(out.params)[0]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
