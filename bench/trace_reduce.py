"""From a profiler trace (`.xplane.pb`) to busy time, idle share, per-op time
and the `breakdown` of the result line.

The device's operations are the events of the "XLA Ops" line of each
`/device:TPU:<n>` plane; the host's spans are the `bench.*` annotations the
harness writes around each solve (`bench.solve`, and inside it
`bench.dispatch`, `bench.wait`, `bench.readback`). The traced window runs
from the start of the first `bench.solve` to the end of the last. Busy time
is the union of the operation intervals inside the window, averaged over the
chips the solve runs on (other chips the host shows are left out); an op's
time is its self time (a while loop's ops nest inside it), and a Pallas
kernel's op is labelled with the kernel's name from the compiled program's
text; an idle gap is a stretch of the window in which no operation runs,
split at the host spans' edges and each piece named by the innermost host
span around it ("between solves" where none is).
"""
from __future__ import annotations

import base64
import dataclasses
import glob
import os
import re
from collections import defaultdict
from pathlib import Path

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
OUTSIDE = "between solves"
TOP = 10


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    idle_share: float
    op_s: dict  # op name -> self seconds in the window, mean over chips
    n_solves: int
    breakdown: dict


def kernel_names(hlo_text: str) -> dict:
    """HLO instruction name -> Pallas kernel name, for each tpu_custom_call
    of a compiled program: the kernel function's name (`..._kernel`) as it
    stands in the serialized Mosaic body."""
    out = {}
    for line in hlo_text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        body = re.search(r'"body":"([A-Za-z0-9+/=]+)"', line)
        if not body:
            continue
        words = re.findall(rb"[A-Za-z_][A-Za-z0-9_]*_kernel\b",
                           base64.b64decode(body.group(1)))
        if words:
            out[short_name(line.strip())] = words[0].decode()
    return out


def short_name(op: str) -> str:
    """`%fusion.87 = f32[...] fusion(...)` -> `fusion.87`."""
    m = re.match(r"%?([^\s=]+)", op)
    return m.group(1) if m else op


def load(path, chips=None):
    """(device ops per chip, host spans) as (name, start_ns, end_ns), of the
    chips with the ids `chips` (all where None)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            if chips is not None and int(
                    plane.name[len(DEVICE_PREFIX):]) not in chips:
                continue
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(short_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns) for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name.startswith(HOST_PREFIX)]
    return devices, host


def self_times(ops, lo, hi):
    """Time of each op inside [lo, hi) less the time of the ops nested in it
    (a while loop holds its body's ops): {name: ns}."""
    out = defaultdict(float)
    stack = []  # [name, start, end, nested ns]

    def close(item):
        out[item[0]] += max(0.0, item[2] - item[1] - item[3])

    for name, s, e in sorted(((n, max(s, lo), min(e, hi)) for n, s, e in ops
                              if e > lo and s < hi),
                             key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    for item in stack:
        close(item)
    return out


def union(intervals, lo, hi):
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy, lo, hi):
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


class Timeline:
    """What the host was doing: its spans cut at their edges into pieces,
    each named by the innermost (shortest) span around it."""

    def __init__(self, spans):
        self.cuts = np.array(sorted({t for _, s, e in spans for t in (s, e)}),
                             np.float64)
        starts = np.array([s for _, s, _ in spans], np.float64)
        ends = np.array([e for _, _, e in spans], np.float64)
        names = [n for n, _, _ in spans]
        self.names = []
        for a, b in zip(self.cuts[:-1], self.cuts[1:]):
            m = (a + b) / 2
            inside = np.flatnonzero((starts <= m) & (m < ends))
            self.names.append(
                names[inside[np.argmin(ends[inside] - starts[inside])]]
                if inside.size else OUTSIDE)

    def attribute(self, lo, hi):
        """[(name, ns)] of the pieces of [lo, hi)."""
        out, t = [], lo
        i = int(np.searchsorted(self.cuts, lo, side="right")) - 1
        while t < hi:
            if i < 0 or i >= len(self.names):
                nxt = self.cuts[0] if i < 0 and self.cuts.size else hi
                name = OUTSIDE
            else:
                nxt, name = self.cuts[i + 1], self.names[i]
            nxt = min(max(nxt, t), hi)
            if nxt == t:  # past the last cut
                nxt, name = hi, OUTSIDE
            out.append((name, nxt - t))
            t, i = nxt, i + 1
        return out


def reduce(devices, host, kernels=None) -> Summary:
    """The window's summary; `kernels` (kernel_names) labels the Pallas
    kernels' ops as `<kernel> (<op>)`."""
    kernels = kernels or {}
    solves = [(s, e) for n, s, e in host if n == "bench.solve"]
    if not devices or not solves:
        raise ValueError("the trace holds no TPU operations or no solve span")
    lo, hi = min(s for s, _ in solves), max(e for _, e in solves)
    window = (hi - lo) * 1e-9
    timeline = Timeline(host)
    busy_ns, op_ns, gap_ns = 0.0, defaultdict(float), defaultdict(float)
    for ops in devices:
        busy = union([(s, e) for _, s, e in ops], lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        for name, ns in self_times(ops, lo, hi).items():
            op_ns[f"{kernels[name]} ({name})" if name in kernels else name] += ns
        for s, e in gaps(busy, lo, hi):
            for name, ns in timeline.attribute(s, e):
                gap_ns[name] += ns
    n = len(devices)
    busy_s = busy_ns * 1e-9 / n
    op_s = {k: v * 1e-9 / n for k, v in op_ns.items()}
    top = lambda d: [[k, v * 1e-9 / n] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return Summary(
        busy_s=busy_s, window_s=window, idle_share=1.0 - busy_s / window,
        op_s=op_s, n_solves=len(solves),
        breakdown={"device_ops": top(op_ns), "idle_gaps": top(gap_ns)})


def newest_trace(trace_dir) -> Path:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(max(files, key=os.path.getmtime))


class Tracer:
    """The JAX profiler around the window, reduced when it stops, over the
    chips with the ids `chips`."""

    def __init__(self, trace_dir, chips=None):
        self.dir = Path(trace_dir)
        self.chips = chips

    def start(self):
        import jax

        self.dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(self.dir))

    def stop(self, kernels=None) -> Summary:
        import jax

        jax.profiler.stop_trace()
        return reduce(*load(newest_trace(self.dir), self.chips), kernels)
