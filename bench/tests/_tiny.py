"""A cell of BENCHMARK.json cut to a size a CPU test run holds: fewer
particles, sweeps and pooled inputs, the kernels' jnp references, and the
window short. Widths and every other setting stay as the cell has them."""
import _paths  # noqa: F401

import spec

SIZES = {"rastrigin-d10.fig1": dict(n_particles=512, iter_bfgs=40, lane_chunk=256)}


def tiny_cell(name):
    cell = spec.load_cell(name)
    s, z = SIZES[name], cell["cfg"]["zeus"]
    z["pso"]["n_particles"] = s["n_particles"]
    z["bfgs"]["iter_bfgs"] = s["iter_bfgs"]
    if "lane_chunk" in z:
        z["lane_chunk"] = s["lane_chunk"]
    cell["mix"] = dict(cell["mix"], pool=6, check_sample=6)
    return cell
