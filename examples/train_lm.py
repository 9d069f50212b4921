"""End-to-end driver: train a small LM for a few hundred steps.

    PYTHONPATH=src python examples/train_lm.py --steps 200

Two modes:
  * default      — AdamW on a reduced gemma2-family model via the full
                   training stack (data pipeline, sharded train_step,
                   checkpointing);
  * --optimizer zeus-lbfgs — the paper's technique as the weight optimizer:
                   multistart L-BFGS (paper §VII-B future work, realized)
                   over the flattened parameter vector of a tiny LM. This is
                   the honest integration scale for quasi-Newton multistart
                   (see DESIGN.md §5): thousands of parameters, not billions.
"""
import argparse

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import get_config, reduce_config
from repro.core import BFGSOptions, LBFGSOptions, batched_lbfgs
from repro.data.pipeline import DataConfig, make_batch
from repro.launch import train as train_launcher
from repro.models import build_model
from repro.train.step import TrainConfig, make_loss_fn


def adamw_mode(steps: int):
    return train_launcher.main([
        "--arch", "gemma2-2b", "--reduced",
        "--steps", str(steps), "--batch", "16", "--seq", "128",
        "--lr", "1e-3", "--log-every", "20",
        "--ckpt-dir", "/tmp/train_lm_ckpt", "--ckpt-every", str(max(steps // 4, 1)),
    ])


def zeus_lbfgs_mode(steps_equiv: int):
    """Multistart L-BFGS training of a tiny LM on a fixed batch."""
    import dataclasses
    cfg = dataclasses.replace(
        reduce_config(get_config("phi3-mini-3.8b")),
        num_layers=2, d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
        d_ff=64, vocab_size=128,
    )
    model = build_model(cfg)
    tcfg = TrainConfig(remat=False, z_loss=0.0)
    dcfg = DataConfig(seed=0, vocab_size=cfg.vocab_size)
    batch = {k: jnp.asarray(v) for k, v in
             make_batch(dcfg, cfg, 8, 32, step=0).items()}
    loss_fn = make_loss_fn(model, tcfg)

    p0 = model.init(jax.random.key(0), jnp.float32)
    flat0, unravel = jax.flatten_util.ravel_pytree(p0)
    D = flat0.shape[0]
    print(f"[zeus-lbfgs] {D} parameters, multistart=8, memory=10")

    def f(theta):
        return loss_fn(unravel(theta), batch)[0]

    starts = flat0[None, :] + 0.05 * jax.random.normal(
        jax.random.key(1), (8, D), jnp.float32
    )
    res = jax.jit(lambda x0: batched_lbfgs(
        f, x0,
        LBFGSOptions(iter_max=steps_equiv, memory=10, theta=1e-3,
                     required_c=4, ad_mode="reverse"),
    ))(starts)
    best = int(jnp.argmin(res.fval))
    l0 = float(f(flat0))
    lb = float(res.fval[best])
    print(f"[zeus-lbfgs] init loss {l0:.4f} -> best lane {lb:.4f} "
          f"({int(res.n_converged)} lanes converged, {int(res.iterations)} sweeps)")
    assert lb < l0, "L-BFGS multistart should beat the init loss"
    print("OK")
    return lb


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "zeus-lbfgs"])
    args = ap.parse_args()
    compile_cache.enable()
    if args.optimizer == "adamw":
        adamw_mode(args.steps)
    else:
        zeus_lbfgs_mode(args.steps)
