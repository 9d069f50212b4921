"""One run of one cell: compile, window, check, metrics.

`run.py` is the command; this module is the run itself, so that a test can
drive a whole run at a small size on the CPU with the device check left out.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

import reference
import spec
import trace_reduce
from stream import Stream

COMPILE_EVENTS = ("/jax/core/compile", "/jax/compilation_cache")
# a traced run traces this many seconds of solves at most (the solve in
# flight then finishes), and this many solves at most: enough for one long
# solve or a dozen short ones, while the profiler's trace of short solves
# (the dijet fit: ~32,000 device ops each) stays quick to write and reduce
TRACE_SECONDS = 5.0
TRACE_SOLVES = 16


def zeus_options(cfg: dict):
    """ZeusOptions from the configuration's `zeus` group, field by field."""
    from repro.core import BFGSOptions, PSOOptions, ZeusOptions

    z = dict(cfg["zeus"])
    return ZeusOptions(pso=PSOOptions(**z.pop("pso", {})),
                       bfgs=BFGSOptions(**z.pop("bfgs", {})),
                       dtype=cfg["dtype"], **z)


def solve_program(cfg: dict, problem):
    """`(key data[, dataset]) -> ZeusResult` through zeus_jit; where the
    configuration sets `toys_per_solve`, zeus under jax.vmap over the toys'
    stacked keys and datasets, as a user batches many fits in one call."""
    import jax
    from repro.core.zeus import zeus, zeus_jit

    opts = zeus_options(cfg)
    dim, lo, hi = cfg["dim"], float(cfg["lower"]), float(cfg["upper"])

    def solve(raw_key, data=None):
        f = problem.program_objective(cfg, data)
        return zeus_jit(f, dim, lo, hi, opts)(jax.random.wrap_key_data(raw_key))

    def toy(raw_key, data=None):
        return zeus(problem.program_objective(cfg, data),
                    jax.random.wrap_key_data(raw_key), dim, lo, hi, opts)

    return jax.vmap(toy) if cfg.get("toys_per_solve") else solve


def precision(cfg: dict):
    """The context a run of `cfg` lives in: JAX's 64-bit mode on exactly
    where the configuration's `dtype` is float64, so that its inputs, its
    compiled solve, the window and the reference's swarm draws all hold the
    precision it states. The mode is restored on leaving."""
    import jax

    return jax.enable_x64(cfg["dtype"] == "float64")


def compile_solve(solve, example_args, kernels=()):
    """The solve compiled ahead of time, and its program's text. Each Pallas
    kernel function named in `kernels` must be in the compiled program
    (trace_reduce.kernel_names), or its path fell back to XLA."""
    import jax

    specs = [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
             for a in example_args]
    exe = jax.jit(solve).lower(*specs).compile()
    text = exe.as_text()
    missing = sorted(set(kernels)
                     - set(trace_reduce.kernel_names(text).values()))
    if missing:
        raise RuntimeError(f"the compiled solve lacks the Pallas kernels "
                           f"{missing}: they did not compile for the chip")
    return exe, text


class CompileCounter:
    """Counts JAX compile and compile-cache events while armed."""

    def __init__(self):
        import jax.monitoring as mon

        self.armed, self.events = False, []
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **_):
        if self.armed and name.startswith(COMPILE_EVENTS):
            self.events.append(name)

    def _on_duration(self, name, _secs, **_):
        self._on_event(name)

    def close(self):
        import jax.monitoring as mon

        mon.unregister_event_listener(self._on_event)
        mon.unregister_event_duration_listener(self._on_duration)


@dataclasses.dataclass
class Window:
    latencies: list
    outputs: list  # None where the solve raised
    elapsed: float
    t_first: float


def drive(exe, stream: Stream, seconds: float, annotate,
          max_solves=None) -> Window:
    """Closed loop, one caller: solves back to back until `seconds` have
    passed (or `max_solves` have run); the solve in flight then finishes and
    counts. Each solve is timed from dispatch until its best_x is on the
    host. The garbage collector is off in the window, so that no collection
    over the answers it keeps stalls a solve."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        return _drive(exe, stream, seconds, annotate, max_solves)
    finally:
        gc.enable()
        gc.unfreeze()


def _drive(exe, stream, seconds, annotate, max_solves):
    import jax

    lat, outs = [], []
    t_first = time.perf_counter()
    i = 0
    while True:
        args = stream.args(i)
        with annotate("bench.solve"):
            t0 = time.perf_counter()
            try:
                with annotate("bench.dispatch"):
                    out = exe(*args)
                with annotate("bench.wait"):
                    jax.block_until_ready(out)
                with annotate("bench.readback"):
                    np.asarray(out.best_x)
            except Exception:  # a solve that raises counts as failed
                traceback.print_exc()
                out = None
            t1 = time.perf_counter()
        lat.append(t1 - t0)
        outs.append(out)
        i += 1
        if t1 - t_first >= seconds or i == max_solves:
            return Window(lat, outs, t1 - t_first, t_first)


def answer_of(out) -> dict:
    import jax

    raw = out.raw
    return jax.device_get({
        "x": raw.x, "fval": raw.fval, "grad_norm": raw.grad_norm,
        "status": raw.status, "n_evals": raw.n_evals,
        "eval_rows": raw.eval_rows,
        "iterations": raw.iterations, "n_converged": out.n_converged,
        "best_x": out.best_x, "best_f": out.best_f,
        "pso_best_f": out.pso_best_f})


def device_info(peak_bytes):
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak_bytes}


def chips_of(exe) -> list:
    """Ids of the devices the compiled solve runs on."""
    import jax

    shardings = jax.tree.leaves((exe.input_shardings, exe.output_shardings))
    return sorted({d.id for sh in shardings for d in sh.device_set})


def program_bytes(exe) -> int:
    """What one execution of the compiled solve holds on its chip at once:
    its arguments, outputs and temporaries (less what outputs alias)."""
    m = exe.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes + m.generated_code_size_in_bytes
               - m.alias_size_in_bytes)


def memory_peak(exe, log):
    """The peak on the fullest chip: the allocator's peak of buffers in use,
    or the compiled solve's own footprint where that is larger (the
    allocator's statistics may leave a program's temporaries out)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peak = max(st.get("peak_bytes_in_use", 0) for st in stats)
    prog = program_bytes(exe)
    log(f"[memory] allocator peak {peak} B, compiled solve {prog} B; "
        f"chip 0 stats {stats[0]}")
    return int(max(peak, prog))


def run(cell: dict, seed: int, seconds: float, trace: bool, t_start: float,
        require_kernel: bool = True, trace_dir: Path | None = None,
        log=lambda m: print(m, file=sys.stderr, flush=True)) -> dict:
    """One run; returns the result line's object. `require_kernel` False
    leaves out the check for the configuration's `kernels` (CPU tests)."""
    with precision(cell["cfg"]):
        return _run(cell, seed, seconds, trace, t_start, require_kernel,
                    trace_dir, log)


def _run(cell, seed, seconds, trace, t_start, require_kernel, trace_dir, log):
    import jax

    cfg, mix = cell["cfg"], cell["mix"]
    problem = spec.problem_module(cfg)
    stream = Stream(mix, cfg, problem, seed)
    solve = solve_program(cfg, problem)
    exe, text = compile_solve(solve, stream.args(0),
                              cfg["kernels"] if require_kernel else ())
    for j in range(cfg.get("warmup_solves", 0)):
        jax.block_until_ready(exe(*stream.warmup_args(j)))

    counter = CompileCounter()
    tracer = None
    if trace:
        tracer = trace_reduce.Tracer(trace_dir, chips_of(exe))
        tracer.start()
    counter.armed = True
    annotate = jax.profiler.TraceAnnotation if trace else no_annotation
    win = (drive(exe, stream, min(seconds, TRACE_SECONDS), annotate,
                 TRACE_SOLVES) if trace else
           drive(exe, stream, seconds, annotate))
    counter.armed = False
    counter.close()
    setup_s = win.t_first - t_start
    summary = (tracer.stop(trace_reduce.kernel_names(text)) if tracer
               else None)
    if counter.events:
        raise RuntimeError(f"compilation inside the window: {counter.events}")
    n = len(win.latencies)
    log(f"[window] {n} solves in {win.elapsed:.3f}s; setup {setup_s:.3f}s")

    peak = memory_peak(exe, log)
    answers = collect(win, stream.toys)
    idx = sample(stream, win, answers)
    per_toy, failed = check(answers, idx, stream, cfg, problem, log)
    compared = set(idx)
    failed += sum(1 for j, a in enumerate(answers)
                  if j not in compared and not answered(a))
    ok, checks = reference.judge(per_toy, cfg["limits"])

    e2e = {"solve_s": win.elapsed / n, "setup_s": setup_s}
    device = device_info(peak)
    metrics = {}
    if trace:
        device["busy_s"], device["window_s"] = summary.busy_s, summary.window_s
        ctx = Context(cfg=cfg, trace=summary, problem=problem,
                      answers=[a for a in answers if a is not None],
                      device_kind=device["kind"])
        for m in cell["per_layer"]:
            v = spec.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    out = {"correct": bool(ok and failed == 0), "attempted": len(answers),
           "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = summary.breakdown
    out["checks"] = checks
    return out


def split_toys(a: dict, toys) -> list:
    """One solve's answer as the answers of its toys: with `toys` None the
    answer itself, else each toy's slice along the leading axis."""
    if toys is None:
        return [a]
    return [{k: v[t] for k, v in a.items()} for t in range(toys)]


def collect(win: Window, toys=None) -> list:
    """Every toy's answer on the host (None for each toy of a solve that
    raised), and the program's state freed."""
    answers = []
    for o in win.outputs:
        answers += ([None] * (toys or 1) if o is None
                    else split_toys(answer_of(o), toys))
    win.outputs = None
    return answers


def sample(stream: Stream, win: Window, answers: list) -> list:
    """The toys compared: a sample drawn from the seed, and in the slowest
    solve the toy that ran the most sweeps."""
    per = stream.per_solve
    first = per * int(np.argmax(win.latencies))
    sweeps = [-1 if a is None else int(a["iterations"])
              for a in answers[first:first + per]]
    slowest = first + int(np.argmax(sweeps))
    return sorted(set(stream.sample(len(answers))) | {slowest})


def answered(a) -> bool:
    return (a is not None and np.isfinite(float(a["best_f"]))
            and int(a["n_converged"]) > 0)


def check(answers, idx, stream, cfg, problem, log, control=False):
    """Readings of the toys `idx` against the reference, each with its own
    key, data and stop rule (with `control`, of the bfloat16 control in the
    program's place); (readings, failed)."""
    per_toy, failed = [], 0
    for j in idx:
        a = answers[j]
        if a is None:
            failed += 1
            continue
        data = stream.data_of(j)
        draws = reference.pso_draws(stream.key_of(j), cfg)
        pso_ref = reference.replay_pso(
            draws, lambda z: problem.value(z, data, cfg), cfg)
        if control:
            a = reference.control_answer(a, problem, cfg, data, draws)
        r, why = reference.readings(a, problem, cfg, data, pso_ref)
        if why:
            failed += 1
            log(f"[check] toy {j} failed: {why}")
        else:
            per_toy.append(r)
    return per_toy, failed


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read: the configuration, the
    answers of the traced solves' toys on the host (in order, the toys of
    a solve that raised left out), the trace's Summary, the problem module
    and the chip's kind."""
    cfg: dict
    trace: object
    problem: object
    answers: list
    device_kind: str


def no_annotation(_name):
    return contextlib.nullcontext()


def print_result(out: dict):
    for k, c in out["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
