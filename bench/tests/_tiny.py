"""A cell of BENCHMARK.json cut to a size a CPU test run holds: fewer
particles, sweeps, toys per solve and pooled inputs, the kernels' jnp
references, and the window short. Widths and every other setting stay as
the cell has them. The dijet fit (512 lanes of D = 4, ~15 sweeps) keeps its
own sizes but fits 4 toys per solve: 4 toys take ~0.1 s on the host's XLA.
It is a held cell: its files are under bench/, and BENCHMARK.json does not
run it (PERF.md §7)."""
import json

import _paths  # noqa: F401

import spec

SIZES = {"rastrigin-d10.fig1": dict(n_particles=512, iter_bfgs=40, lane_chunk=256),
         "rastrigin-d10.fig1-mega": dict(n_particles=512, iter_bfgs=40,
                                         lane_chunk=256),
         "dijet-fit.toys": dict(n_particles=512, iter_bfgs=300,
                                toys_per_solve=4)}
SOLVES = 6  # pooled, and the toys compared, in solves' worth of toys
# held cell -> (configuration file, traffic)
HELD = {"dijet-fit.toys": ("dijet-fit.json", "toys")}


def held_cell(name):
    """A held cell resolved as spec.load_cell resolves a cell of
    BENCHMARK.json, with the end-to-end metrics every cell reports."""
    config, traffic = HELD[name]
    with open(spec.BENCH / "configs" / config) as fh:
        cfg = json.load(fh)
    with open(spec.BENCH / "traffic" / f"{traffic}.json") as fh:
        mix = json.load(fh)
    bench = spec.load_benchmark()
    return {"name": name, "config": cfg["name"], "traffic": traffic,
            "chips": 1, "cfg": cfg, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"]
                           if "workloads" not in m],
            "per_layer": []}


def tiny_cell(name):
    cell = held_cell(name) if name in HELD else spec.load_cell(name)
    s, cfg = SIZES[name], cell["cfg"]
    z = cfg["zeus"]
    z["pso"]["n_particles"] = s["n_particles"]
    z["bfgs"]["iter_bfgs"] = s["iter_bfgs"]
    if "lane_chunk" in z:
        z["lane_chunk"] = s["lane_chunk"]
    if "toys_per_solve" in cfg:
        cfg["toys_per_solve"] = s["toys_per_solve"]
    toys = SOLVES * cfg.get("toys_per_solve", 1)
    cell["mix"] = dict(cell["mix"], pool=toys, check_sample=toys)
    return cell
