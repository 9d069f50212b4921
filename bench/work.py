"""The phase-2 work a solve requires, and the least time the chip needs for it.

Counted at the unpadded D, in bytes of the configuration's dtype, for the
lane-sweeps in which a lane was still active: no pad column, no masked lane and no speculative
rung past the one a lane needs counts as required. One lane-sweep needs

- one line-search trial: x + a·p (2D flops, x and p read) and one value row;
- one value+grad row at the accepted point;
- the dense BFGS update of H with the new direction: H read and written
  (2·D² floats), s, y and g read and p written (4·D floats); Hy, y·Hy, three
  rank-one terms and p = -H'g are 10·D² flops to leading order, plus 6·D;

and each lane starts with one value+grad row. The per-row objective costs
are the problem's `row_work`.

The lane-sweeps come from the program's per-lane `n_evals` counter, which
books `vg_cost` (c) per value+grad call and one per ladder rung evaluated.
A batched or megakernel sweep evaluates the whole ladder of `ls_iters` (K)
rungs, so n_evals = c + s·(K + c) gives s exactly; a per-lane sweep's Armijo
search stops at the first rung accepted, 1 to K of them, so its counter
bounds s and the required work is not counted.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

FIXED_LADDER = ("batched", "megakernel")
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def itemsize(cfg: dict) -> int:
    """Bytes of one number in the configuration's dtype."""
    return np.dtype(cfg["dtype"]).itemsize


def sweep_mode(cfg: dict) -> str:
    """The phase-2 path the configuration runs (the program's default is
    per_lane)."""
    z = cfg["zeus"]
    return z.get("sweep_mode") or z.get("bfgs", {}).get("sweep_mode",
                                                        "per_lane")


def update_work(d: int, size: int):
    """(flops, bytes) of one lane's H update and new direction, with
    numbers of `size` bytes."""
    return 10 * d * d + 6 * d, (2 * d * d + 4 * d) * size


def lane_sweep_work(d: int, rows: dict, size: int):
    vf, vb = rows["value"]
    gf, gb = rows["value_grad"]
    uf, ub = update_work(d, size)
    trial_f, trial_b = 2 * d, 2 * d * size
    return trial_f + vf + gf + uf, trial_b - d * size + vb + gb + ub


def lane_sweeps(n_evals, vg_cost: int, ladder: int):
    """Active sweeps per lane from n_evals of a whole-ladder sweep, or None
    where the counter does not follow its convention (another ladder or
    cost booking)."""
    n = np.asarray(n_evals, np.int64) - vg_cost
    per = ladder + vg_cost
    if np.any(n < 0) or np.any(n % per):
        return None
    return n // per


def sweep_range(cfg: dict, n_evals, vg_cost: int):
    """(least, most) active sweeps per lane that its n_evals admits on the
    configuration's path, or None where some lane's counter admits none.
    On a whole-ladder path least == most == lane_sweeps; on the per-lane
    path n_evals = c + Σ (i_k + c) over the sweeps, 1 ≤ i_k ≤ K."""
    ladder = cfg["zeus"]["bfgs"].get("ls_iters", 20)
    if sweep_mode(cfg) in FIXED_LADDER:
        s = lane_sweeps(n_evals, vg_cost, ladder)
        return None if s is None else (s, s)
    n = np.asarray(n_evals, np.int64) - vg_cost
    least, most = -(-n // (ladder + vg_cost)), n // (1 + vg_cost)
    if np.any(n < 0) or np.any(least > most):
        return None
    return least, most


def solve_work(cfg: dict, problem, n_evals):
    """Required (flops, bytes) of one solve's phase 2, or None (a counter
    that does not decode, or a path whose counter gives no sweep count)."""
    if sweep_mode(cfg) not in FIXED_LADDER:
        return None
    ladder = cfg["zeus"]["bfgs"].get("ls_iters", 20)
    s = lane_sweeps(n_evals, problem.vg_cost(cfg), ladder)
    if s is None:
        return None
    d, rows = cfg["dim"], problem.row_work(cfg)
    sf, sb = lane_sweep_work(d, rows, itemsize(cfg))
    gf, gb = rows["value_grad"]
    lanes, sweeps = len(s), int(s.sum())
    return sweeps * sf + lanes * gf, sweeps * sb + lanes * gb


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
