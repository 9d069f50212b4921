"""Device time per stage of the solve, from the `jax.named_scope`s the
program puts around its stages (`zeus.phase1`, `zeus.phase2` and its sweep
stages `zeus.phase2.<stage>`, `zeus.finale`).

The profiler's trace names each op by its HLO instruction name and carries
no scope, so the scope comes from the text of the compiled program: each
instruction's `metadata={op_name="..."}` holds the name stack it was traced
under, and the innermost `zeus.*` name in it is the op's stage. The text is
compiled anew here, with the persistent compile cache off: the cache's key
leaves metadata out, so the executable the harness ran may come from a tree
with other scopes. Every op of the trace is looked up in the fresh text,
and a kernel's label checked against it; any mismatch gives None, never
time put down to the wrong stage.
"""
from __future__ import annotations

import contextlib
import json
import re
import sys
import time

import numpy as np

import harness
import trace_reduce

INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=")
OP_NAME = re.compile(r'op_name="([^"]*)"')
SCOPE = re.compile(r"zeus(?:\.\w+)+")  # under vmap it reads vmap(zeus....)
KERNEL_KEY = re.compile(r"^(\S+) \((\S+)\)$")  # trace_reduce's label

_texts: dict = {}  # one compile per configuration and process


def scope_of(text: str) -> dict:
    """{instruction name: innermost `zeus.*` scope of its op_name, or None}
    for every instruction line of a compiled program's text."""
    out = {}
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if not m:
            continue
        name = OP_NAME.search(line)
        found = SCOPE.findall(name.group(1)) if name else []
        out[m.group(1)] = found[-1] if found else None
    return out


@contextlib.contextmanager
def _no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def program_text(cfg: dict, problem) -> str:
    """The text of the solve the harness runs for `cfg`, compiled by the
    harness's own functions from this tree (persistent cache off), once
    per process."""
    key = json.dumps(cfg, sort_keys=True)
    if key not in _texts:
        args = (np.zeros(2, np.uint32),)
        data = problem.make_data(cfg, np.random.default_rng(0))
        if data is not None:
            args += (data,)
        if cfg.get("toys_per_solve"):  # the solve's inputs stacked over toys
            args = tuple(np.stack([a] * cfg["toys_per_solve"]) for a in args)
        t0 = time.perf_counter()
        with _no_persistent_cache(), harness.precision(cfg):
            _, _texts[key] = harness.compile_solve(
                harness.solve_program(cfg, problem), args)
        print(f"[scopes] program text compiled in "
              f"{time.perf_counter() - t0:.3f}s", file=sys.stderr, flush=True)
    return _texts[key]


def op_scopes(op_keys, text: str):
    """{op key of a trace Summary: its scope or None}, or None where an op
    is not in `text` or its kernel label (or the lack of one) does not
    match the text's."""
    scopes, kernels = scope_of(text), trace_reduce.kernel_names(text)
    out = {}
    for key in op_keys:
        m = KERNEL_KEY.match(key)
        op, label = (m.group(2), m.group(1)) if m else (key, None)
        if op not in scopes or kernels.get(op) != label:
            return None
        out[key] = scopes[op]
    return out


def seconds_per_scope(ctx, kernels: bool = True):
    """{scope or None: device seconds per solve} of the traced solves; ops
    with no `zeus.*` scope under None. The values sum to busy_s / n_solves;
    with `kernels` False the Pallas kernels' ops are left out. None without
    a trace or where the trace and the program disagree."""
    if ctx.trace is None:
        return None
    ops = op_scopes(ctx.trace.op_s, program_text(ctx.cfg, ctx.problem))
    if ops is None:
        return None
    out = {}
    for key, scope in ops.items():
        if kernels or not KERNEL_KEY.match(key):
            s = ctx.trace.op_s[key] / ctx.trace.n_solves
            out[scope] = out.get(scope, 0.0) + s
    return out


def scope_ms(ctx, scope: str, kernels: bool = True):
    """Device ms per solve under `scope` (seconds_per_scope), or None where
    no op of the trace has it."""
    whole = seconds_per_scope(ctx)
    if whole is None or scope not in whole:
        return None
    part = whole if kernels else seconds_per_scope(ctx, kernels=False)
    return part.get(scope, 0.0) * 1e3
