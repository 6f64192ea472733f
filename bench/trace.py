"""The reduction from a profiler trace to device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
lists, all on the profiler's one clock: for every device plane its op
events as ``[op, start_ns, duration_ns, module]`` (``op`` shortened to the
instruction's name, opcode and custom-call target; ``module`` the jitted
program the op ran in), and the host's ``bench.*`` annotations as
``[name, start_ns, duration_ns]``. ``reduce`` works on that form only, so
a small recorded trace in the same form checks it without a chip.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
# "%fusion.3 = bf16[..]{..} fusion(...), ..., custom_call_target="x""
_HLO = re.compile(r"^(%\S+) = .*?\s([a-z][\w-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_op(text: str) -> str:
    """``%closed_call.29 custom-call tpu_custom_call`` from the op's HLO."""
    m = _HLO.match(text)
    if not m:
        return text[:80]
    t = _TARGET.search(text)
    return " ".join([m.group(1), m.group(2)] + ([t.group(1)] if t else []))


def short_module(name: str) -> str:
    """``jit_decode_chunk_paged`` from ``jit_decode_chunk_paged(2287...)``."""
    return name.split("(", 1)[0]


def _attribute(ops, modules) -> list:
    """Each op with the module whose event holds its start."""
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    out = []
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = modules[i][2] if i >= 0 and s < modules[i][1] else ""
        out.append([short_op(name), s, d, mod])
    return out


def load(log_dir: str | Path) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, float(e.start_ns),
                                float(e.duration_ns)) for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend((float(e.start_ns),
                                    float(e.start_ns + e.duration_ns),
                                    short_module(e.name))
                                   for e in line.events)
            if ops:
                devices[plane.name] = _attribute(ops, modules)
        else:
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def save(trace: dict, path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def read(path: Path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def window(trace: dict) -> tuple[float, float]:
    """The traced window: the host's ``bench.window`` annotation."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == "bench.window"]
    if not spans:
        raise RuntimeError("the trace holds no bench.window annotation")
    return spans[0]


def reduce(trace: dict, kernel=None) -> dict:
    """Device busy time, op totals and idle gaps inside the window.

    busy_s: the union of op intervals per device, averaged over devices.
    ``kernel``: a predicate ``kernel(op, module)``; kernel_s is the summed
    device time of matching events (0.0 when none match) and kernel_calls
    their count, averaged over devices.
    device_ops: device 0's ops, named ``module op``, by self time (a loop's
    event spans the ops of its body, which count for themselves).
    idle_gaps: the longest gaps with no op on device 0, each named by the
    host annotation that overlaps it most."""
    lo, hi = window(trace)
    win = (hi - lo) * 1e-9
    busy, kern, calls, ops = [], [], [], {}
    gaps0 = []
    for i, (_, evs) in enumerate(sorted(trace["devices"].items())):
        u = _union(_clip([(s, s + d) for _, s, d, _ in evs], lo, hi))
        busy.append(sum(b - a for a, b in u) * 1e-9)
        hits = [min(s + d, hi) - max(s, lo) for n, s, d, m in evs
                if kernel is not None and kernel(n, m)
                and min(s + d, hi) > max(s, lo)]
        kern.append(sum(hits) * 1e-9)
        calls.append(len(hits))
        if i == 0:
            ops = _self_times(evs, lo, hi)
            edges = [lo] + [x for ab in u for x in ab] + [hi]
            gaps0 = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    host = [(n[len(HOST_PREFIX):], s, s + d) for n, s, d in trace["host"]
            if n != "bench.window"]

    def doing(a, b):
        best, what = 0.0, "none"
        for n, s, e in host:
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, what = ov, n
        return what

    longest = sorted(gaps0, key=lambda g: g[0] - g[1])[:10]
    n_dev = max(1, len(busy))
    return {
        "window_s": win,
        "busy_s": sum(busy) / n_dev,
        "kernel_s": sum(kern) / n_dev,
        "kernel_calls": sum(calls) / n_dev,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": [[doing(a, b), (b - a) * 1e-9] for a, b in longest],
        "idle_by_host": _idle_by_host(gaps0, host),
    }


def _self_times(evs, lo, hi) -> dict:
    """Seconds inside [lo, hi] that each ``module op`` ran outside the ops
    nested in it (a loop's event spans its body's ops)."""
    out: dict[str, float] = {}
    stack: list = []                      # [name, end, own time]

    def close(entry):
        out[entry[0]] = out.get(entry[0], 0.0) + entry[2] * 1e-9

    for n, s, d, m in sorted(evs, key=lambda e: (e[1], -e[2])):
        a, b = max(s, lo), min(s + d, hi)
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if b <= a:
            continue
        if stack:
            stack[-1][2] -= b - a
        stack.append([f"{m} {n}".strip(), s + d, b - a])
    for entry in stack:
        close(entry)
    return out


def _idle_by_host(gaps, host) -> dict:
    """Idle device seconds, split by the host annotation they overlap."""
    out: dict[str, float] = {}
    for a, b in gaps:
        for n, s, e in host:
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[n] = out.get(n, 0.0) + ov * 1e-9
    return out
