"""The readings that set the limits of `correct`, on the chip.

    python3 bench/calibrate.py --workload rastrigin-d10.fig1 --seconds 1 \
        --seeds 101,102,103 --control 3

For each seed, one window of the cell's own traffic at its own size (the
compiled solve program shared by all seeds), then the readings of the
program's solves against the reference, and for the first `--control` seeds
also the readings of the control. The control is the nearest precision
below the one the configuration states: for float64, the program's own
float32 path (the configuration with `dtype` float32, compiled apart, on
the same keys and data, in a window of its own); for float32, the
reference in bfloat16 in the program's place. One JSON line per seed: each
number as a run reads it over the toys it compares (reference.aggregate).
The lower reading of a limit is the largest the program gives over a dozen
seeds or more; the upper, the smallest the control gives.
"""
import argparse
import json
import sys
import time

import run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(run.BENCH))
    import spec

    cell = spec.load_cell(args.workload, run.ROOT)
    run.enable_compile_cache()
    run.require_chips(cell["chips"])
    sys.path.insert(0, str(run.ROOT / "src"))
    for line in calibrate(cell, [int(s) for s in args.seeds.split(",")],
                          args.seconds, args.control):
        print(json.dumps(line), flush=True)


def calibrate(cell, seeds, seconds, n_control, require_kernel=True):
    import harness

    cfg = cell["cfg"]
    problem = harness.spec.problem_module(cfg)
    lower = program_control(cell)
    exe = exe_lower = None
    for k, seed in enumerate(seeds):
        control = k < n_control
        with harness.precision(cfg):
            exe, line = _seed(cell, problem, exe, seed, seconds,
                              control and lower is None, require_kernel)
        if control and lower is not None:
            with harness.precision(lower["cfg"]):
                exe_lower, low = _seed(lower, problem, exe_lower, seed,
                                       seconds, False, require_kernel)
            line["control"] = low["program"]
            line["control_failed"] = low["program_failed"]
        yield line


# the program's own path one precision below the configuration's
PROGRAM_BELOW = {"float64": "float32"}


def program_control(cell):
    """The cell run on the program's own path at the precision below the
    configuration's, where the program has one; else None."""
    below = PROGRAM_BELOW.get(cell["cfg"]["dtype"])
    if below is None:
        return None
    return dict(cell, cfg=dict(cell["cfg"], dtype=below))


def _seed(cell, problem, exe, seed, seconds, control, require_kernel):
    """(the compiled solve, one seed's line); compiles on the first seed."""
    import jax

    import harness
    import reference
    from stream import Stream

    cfg = cell["cfg"]
    log = lambda m: print(m, file=sys.stderr, flush=True)
    stream = Stream(cell["mix"], cfg, problem, seed)
    if exe is None:
        exe, _ = harness.compile_solve(
            harness.solve_program(cfg, problem), stream.args(0),
            cfg["kernels"] if require_kernel else ())
        for j in range(cfg.get("warmup_solves", 0)):
            jax.block_until_ready(exe(*stream.warmup_args(j)))
    t0 = time.perf_counter()
    win = harness.drive(exe, stream, seconds, harness.no_annotation)
    answers = harness.collect(win, stream.toys)
    idx = harness.sample(stream, win, answers)
    line = {"seed": seed, "solves": len(win.latencies), "toys": len(answers),
            "compared": len(idx), "window_s": win.elapsed}
    for tag in ("program", "control") if control else ("program",):
        per_solve, failed = harness.check(answers, idx, stream, cfg, problem,
                                          log, control=tag == "control")
        line[tag] = reference.aggregate(per_solve) or None
        line[tag + "_failed"] = failed
    line["check_s"] = time.perf_counter() - t0 - win.elapsed
    return exe, line


if __name__ == "__main__":
    main()
