"""Find a cell's pieces by the names in BENCHMARK.json.

A cell (`workloads` entry) names a configuration and a traffic mix; each is a
file of its own (`configs/<file>`, `traffic/<traffic>.json`), the
configuration names its problem (`problems/<problem>.py`), and each
per-layer metric is a reader `metrics/<name>.py`. Adding a cell, a mix, a
problem or a metric adds files and entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path):
    """Import a file by path (names may hold '-' and '.')."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as fh:
        return json.load(fh)


def _applies(metric: dict, cell_name: str) -> bool:
    return cell_name in metric.get("workloads", [cell_name])


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The resolved cell: its entry, configuration, traffic and metrics."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = dict(cells[name])
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    with open(root / entry["file"]) as fh:
        cell["cfg"] = json.load(fh)
    with open(root / "bench" / "traffic" / f"{cell['traffic']}.json") as fh:
        cell["mix"] = json.load(fh)
    cell["end_to_end"] = [m for m in bench["end_to_end"] if _applies(m, name)]
    cell["per_layer"] = [m for m in bench["per_layer"] if _applies(m, name)]
    return cell


def problem_module(cfg: dict):
    return load_module(BENCH / "problems" / f"{cfg['problem']}.py")


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py")
