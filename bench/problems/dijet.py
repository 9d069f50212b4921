"""The CMS 4-parameter dijet mass-spectrum fit (ZEUS paper §V-G, Fig. 5).

Each solve fits one Poisson toy spectrum: 40 bins of width 125 GeV over
1–6 TeV at √s = 13 TeV, drawn around the truth (log p0, p1, p2, p3) =
(−2, 10, 4.5, 0.3). The program gets its own log-space Poisson NLL over the
toy's counts (`repro.core.objectives.make_dijet_nll`, as
examples/fit_dijet.py builds it). The reference below is written anew: the
per-bin mean NLL Σ(μ − n·log μ)/40 with

    log μ_b = log p0 + p1·log(1 − x_b) − (p2 + p3·log x_b)·log x_b + log w_b,
    x_b = m_b/√s (m_b the bin's centre, w_b its width),

and its analytic gradient Σ(μ − n)·∂log μ/∂θ / 40, over rows, in the array
module `xp` it is given (numpy float64 for the check, jax.numpy in a lower
precision for the control).
"""
import numpy as np

EDGES = np.linspace(1000.0, 6000.0, 41)  # GeV
SQRT_S = 13000.0  # GeV
TRUTH = np.array([-2.0, 10.0, 4.5, 0.3])
CENTERS = 0.5 * (EDGES[:-1] + EDGES[1:])
LOG_W = np.log(EDGES[1:] - EDGES[:-1])
LOG_X = np.log(CENTERS / SQRT_S)
LOG_1MX = np.log1p(-CENTERS / SQRT_S)
# ∂log μ_b/∂θ, one row per parameter: (4, 40)
DLOG_MU = np.stack([np.ones_like(LOG_X), LOG_1MX, -LOG_X, -LOG_X * LOG_X])


def program_objective(cfg, data):
    from repro.core.objectives import make_dijet_nll

    return make_dijet_nll(EDGES, data)


def make_data(cfg, rng):
    """One Poisson toy spectrum around the truth, in the configuration's
    dtype (counts up to ~1e5 are exact in float32 too)."""
    mu = np.exp(_log_mu(TRUTH[None], np))[0]
    return rng.poisson(mu).astype(cfg["dtype"])


def _log_mu(x, xp):
    logp0, p1, p2, p3 = (x[..., i:i + 1] for i in range(4))
    lx = xp.asarray(LOG_X, x.dtype)
    return (logp0 + p1 * xp.asarray(LOG_1MX, x.dtype) - (p2 + p3 * lx) * lx
            + xp.asarray(LOG_W, x.dtype))


def value(x, data, cfg, xp=np):
    lm = _log_mu(x, xp)
    n = xp.asarray(data, x.dtype)
    return xp.sum(xp.exp(lm) - n * lm, axis=-1) / lm.shape[-1]


def grad(x, data, cfg, xp=np):
    lm = _log_mu(x, xp)
    r = xp.exp(lm) - xp.asarray(data, x.dtype)
    return r @ xp.asarray(DLOG_MU.T, x.dtype) / lm.shape[-1]


def vg_cost(cfg):
    """Objective-eval equivalents the program books per value+grad call in
    forward mode: one primal and one tangent pass per parameter."""
    return 1 + cfg["dim"]
