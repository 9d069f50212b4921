"""The Pallas kernels compile for a TPU v5e, checked without a chip.

Each test compiles one `*_pallas` kernel with interpret=False for a
described `v5e:2x2` topology, at the shapes of the paper-scale smoke solve
(chip_smoke.py), and checks that the program holds the Mosaic kernel
(`tpu_custom_call`). The chip's tiling and VMEM refusals only show up here:
interpret mode accepts block shapes and working sets the chip compiler
refuses. Nothing runs, so these tests say nothing about values or times.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import bfgs_update, direction, fused_obj, meanfield_step
from repro.kernels import pso_step
from repro.kernels import sweep_megakernel as smk
from repro.kernels.ops import MEGAKERNEL_MAX_DIM

f32 = jnp.float32
K = 20  # the engine's default ls_iters ladder


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2; the compile cache is off while the
    module runs (a compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name", ["rastrigin", "ackley"])
@pytest.mark.parametrize("grad", [False, True], ids=["value", "value_grad"])
def test_fused_objective(one_chip, name, grad):
    kernel = (fused_obj.fused_value_grad_pallas if grad
              else fused_obj.fused_value_pallas)
    _compile(lambda x: kernel(name, x, dim=10), ((8192, 128), f32),
             sharding=one_chip)


@pytest.mark.parametrize("B,D", [(8192, 128), (16, MEGAKERNEL_MAX_DIM)])
def test_guarded_update_direction(one_chip, B, D):
    _compile(bfgs_update.guarded_update_direction_pallas,
             ((B, D, D), f32), ((B, D), f32), ((B, D), f32), ((B, D), f32),
             ((B,), f32), sharding=one_chip)


@pytest.mark.parametrize("B,D", [(8192, 10), (8, 10),
                                 (128, bfgs_update.LANE_MINOR_MAX_DIM)])
def test_guarded_update_direction_lane_minor(one_chip, B, D):
    _compile(bfgs_update.guarded_update_direction_lanes_pallas,
             ((D, D, B), f32), ((D, B), f32), ((D, B), f32), ((D, B), f32),
             ((B,), f32), sharding=one_chip)


def test_direction(one_chip):
    _compile(direction.direction_pallas, ((8192, 128, 128), f32),
             ((8192, 128), f32), sharding=one_chip)


def test_pso_step(one_chip):
    N = 100_000
    _compile(lambda *a: pso_step.pso_step_pallas(*a, 0.5, 1.2, 1.5),
             ((N, 128), f32), ((N, 128), f32), ((N, 128), f32),
             ((128,), f32), ((N, 128), f32), ((N, 128), f32),
             sharding=one_chip)


@pytest.mark.parametrize("isotropic", [True, False])
def test_meanfield_step(one_chip, isotropic):
    N = 100_000
    _compile(lambda *a: meanfield_step.meanfield_step_pallas(
        *a, 0.5, 0.3, 0.1, isotropic=isotropic),
        ((N, 128), f32), ((N, 128), f32), ((128,), f32), ((N, 128), f32),
        sharding=one_chip)


@pytest.mark.parametrize("B,D", [(8192, 128), (16, MEGAKERNEL_MAX_DIM)])
def test_megakernel_full(one_chip, B, D):
    alphas = (0.5 ** np.arange(K)).astype(np.float32)
    _compile(lambda X, P, G, H, act, rhs: smk.sweep_megakernel_full_pallas(
        "rastrigin", X, P, G, H, act, rhs, alphas, dim=10),
        ((B, D), f32), ((B, D), f32), ((B, D), f32), ((B, D, D), f32),
        ((B,), jnp.bool_), ((K, B), f32), sharding=one_chip)


def test_megakernel_commit(one_chip):
    # its body is the full kernel's stage 4, so the cap is checked there
    B, D = 8192, 128
    _compile(lambda X, P, G, H, act, alpha: smk.sweep_megakernel_commit_pallas(
        "rastrigin", X, P, G, H, act, alpha, dim=10),
        ((B, D), f32), ((B, D), f32), ((B, D), f32), ((B, D, D), f32),
        ((B,), jnp.bool_), ((B,), f32), sharding=one_chip)
