"""The chip benchmark of ZEUS: one run of one cell.

    python3 bench/run.py --workload rastrigin-d10.fig1 --seed 7 \
        --seconds 30 --trace 0

Run from the root of a checkout on a machine with the TPU chips the cell
asks for. Set-up (process start, TPU init, the solve program compiled ahead
of time or loaded from the compile cache in `.jax_cache/`, the inputs drawn
from the seed, the configuration's warm-up solves) is `setup_s`; then solves
run back to back for `--seconds`; then every solve of a sample is compared
with the plain reference (bench/reference.py). The last line of stdout is
one JSON object: `correct`, `attempted`, `failed`, `metrics`, `device`,
with `--trace 1` also `breakdown`, and last `checks`, each compared number
beside its limit (also the last lines of stderr). Without a TPU, or with
fewer chips than the cell asks for, it exits nonzero and prints no result.
`--keep-trace DIR` keeps the profiler's trace of a traced run in DIR.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None)
    return ap.parse_args(argv)


def enable_compile_cache():
    """The persistent compile cache at a fixed path inside the checkout,
    for the benchmark and for any program code that reads the variable."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(n: int):
    """A TPU with at least `n` chips, or exit nonzero with no result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found {devs[0].platform!r} "
                 f"({devs[0].device_kind})")
    if len(devs) < n:
        sys.exit(f"bench: the cell needs {n} chips; JAX found {len(devs)}")


def main(argv=None):
    args = parse(argv)
    sys.path.insert(0, str(BENCH))
    import spec

    cell = spec.load_cell(args.workload, ROOT)
    enable_compile_cache()
    require_chips(cell["chips"])
    sys.path.insert(0, str(ROOT / "src"))
    import harness

    trace_dir = BENCH / ".traces" / f"{args.workload}-{args.seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, trace_dir=trace_dir)
    finally:
        if args.keep_trace and trace_dir.exists():
            shutil.copytree(trace_dir, args.keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    harness.print_result(out)


if __name__ == "__main__":
    main()
