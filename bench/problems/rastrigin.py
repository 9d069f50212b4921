"""Rastrigin (ZEUS paper §V-B), as the program's registered objective.

The program gets its own `rastrigin`, so its fused value/grad Pallas kernels
run. The reference below is written anew: value and analytic gradient over
rows, in the array module `xp` it is given (numpy float64 for the check,
jax.numpy in a lower precision for the control).
"""
import numpy as np


def program_objective(cfg, data):
    from repro.core.objectives import get_objective

    return get_objective("rastrigin").fn


def make_data(cfg, rng):
    return None


def value(x, data, cfg, xp=np):
    return 10.0 * x.shape[-1] + xp.sum(
        x * x - 10.0 * xp.cos(2.0 * np.pi * x), axis=-1)


def grad(x, data, cfg, xp=np):
    return 2.0 * x + 20.0 * np.pi * xp.sin(2.0 * np.pi * x)


def vg_cost(cfg):
    """Objective-eval equivalents the program books per value+grad row of a
    fused objective kernel (its `n_evals` convention)."""
    return 2


def row_work(cfg):
    """Required (flops, bytes) of one value row and of one value+grad row at
    the unpadded D, in the configuration's dtype: per coordinate 2πx, cos,
    x², scale, subtract and the sum's add; the gradient adds sin, scale, 2x
    and an add."""
    d, b = cfg["dim"], np.dtype(cfg["dtype"]).itemsize
    return {"value": (6 * d, d * b + b), "value_grad": (11 * d, d * b + b + d * b)}
