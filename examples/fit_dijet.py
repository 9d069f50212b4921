"""Real-world application (paper §V-G / Fig. 5): dijet mass-spectrum fit.

    PYTHONPATH=src python examples/fit_dijet.py

Simulates a falling dijet mass spectrum with Poisson noise, fits the
4-parameter CMS dijet function by maximum likelihood with ZEUS, and prints
the pull distribution — the paper's acceptance criterion is pulls centered
on zero and mostly within ±2σ.
"""
import jax

jax.config.update("jax_enable_x64", True)  # the paper fits in double precision

import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import BFGSOptions, PSOOptions, ZeusOptions, zeus
from repro.core.objectives import (
    dijet_rate,
    make_dijet_nll,
    simulate_dijet_counts,
)

TRUE = np.array([-2.0, 10.0, 4.5, 0.3])  # logp0, p1, p2, p3
# (~1e5 events in the first bin falling to ~1 at 6 TeV — a
#  realistic LHC dijet yield profile)


def main():
    edges = np.linspace(1000.0, 6000.0, 41)  # GeV
    counts = simulate_dijet_counts(TRUE, edges, seed=7)
    nll = make_dijet_nll(edges, counts)

    opts = ZeusOptions(
        pso=PSOOptions(n_particles=512, iter_pso=10),
        bfgs=BFGSOptions(iter_bfgs=300, theta=1e-2, required_c=32,
                         linesearch="armijo", ad_mode="forward"),
        dtype="float64",
    )
    # parameter box around physically sensible values
    res = jax.jit(lambda k: zeus(nll, k, 4, -5.0, 15.0, opts))(jax.random.key(3))

    fit = np.asarray(res.best_x, np.float64)
    print(f"true params : {TRUE}")
    print(f"fit  params : {fit.round(4)}")
    print(f"nll(fit)    : {float(res.best_f):.2f}  "
          f"nll(true)   : {float(nll(jnp.asarray(TRUE))):.2f}")

    centers = 0.5 * (edges[:-1] + edges[1:])
    widths = edges[1:] - edges[:-1]
    pred = np.asarray(dijet_rate(jnp.asarray(fit), jnp.asarray(centers))) * widths
    sigma = np.sqrt(np.maximum(pred, 1.0))
    pulls = (counts - pred) / sigma

    print(f"pulls mean={pulls.mean():.3f} std={pulls.std():.3f} "
          f"max|pull|={np.abs(pulls).max():.2f}")
    frac2 = float(np.mean(np.abs(pulls) <= 2.0))
    print(f"fraction within ±2σ: {frac2:.1%} (paper: 'mostly within ±2σ')")
    assert float(res.best_f) <= float(nll(jnp.asarray(TRUE))) + 1.0
    assert frac2 >= 0.9
    print("OK — fit quality matches Fig. 5 criteria")


if __name__ == "__main__":
    compile_cache.enable()
    main()
