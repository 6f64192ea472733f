"""The program's own host spans, read from the profiler's trace.

The program marks its host work with ``repro.*`` annotations
(``repro.orchestrator.obs.tracing.span``): ``repro.step`` around one
``ContinuousScheduler.step``, and inside it ``repro.admit``,
``repro.prefill`` (``.dispatch``, ``.wait``, ``.insert``), ``repro.decode``
(``.alloc``, ``.dispatch``, ``.wait``, ``.readback``, ``.walk``),
``repro.observe`` and ``repro.write_state``; ``repro.compile`` around a
serve-step compile. They lie on the clock of the device's ops.

``load`` reads them, with their attributes, from the ``.xplane.pb`` that
``bench/run.py`` writes under ``TRACE_DIR``, beside the device ops and the
host's ``bench.*`` annotations that ``bench.trace.load`` reads, into one
plain form: ``bench.trace``'s, plus ``"program"``, a list of
``[name, start_ns, duration_ns, {attribute: value}]``. ``idle_by_span``,
``host_idle_share`` and ``decode_turnarounds_ms`` work on that form only,
so a small synthetic trace checks them without a chip.
"""

from __future__ import annotations

import glob
import os
import statistics
from pathlib import Path

from bench import trace as trace_mod
from bench.spec import BENCH

# where bench/run.py has the profiler write its trace
TRACE_DIR = BENCH / ".work" / "trace"
PREFIX = "repro."
WAIT = ".wait"

_parsed: dict = {}          # (path, mtime, size) -> the loaded form


def load(log_dir: str | Path | None = None) -> dict | None:
    """The newest trace under ``log_dir`` (``TRACE_DIR`` by default) in
    the plain form, parsed once per file; None when there is no trace or
    the program wrote no ``repro.*`` span into it."""
    log_dir = Path(log_dir or TRACE_DIR)
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return None
    st = os.stat(paths[-1])
    key = (paths[-1], st.st_mtime_ns, st.st_size)
    if key not in _parsed:
        _parsed.clear()
        _parsed[key] = _read(log_dir, paths[-1])
    out = _parsed[key]
    return out if out["program"] else None


def _read(log_dir: Path, path: str) -> dict:
    from jax.profiler import ProfileData
    out = trace_mod.load(log_dir)
    program = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    program.append([e.name, float(e.start_ns),
                                    float(e.duration_ns), dict(e.stats)])
    out["program"] = sorted(program, key=lambda e: (e[1], -e[2]))
    return out


def _device_gaps(t: dict, lo: float, hi: float) -> list:
    """Stretches of [lo, hi] with no op on device 0 (the device
    ``bench.trace.reduce`` takes its gaps from); all of it with none."""
    if not t["devices"]:
        return [(lo, hi)]
    evs = t["devices"][sorted(t["devices"])[0]]
    u = trace_mod._union(trace_mod._clip(
        [(s, s + d) for _, s, d, _ in evs], lo, hi))
    edges = [lo] + [x for ab in u for x in ab] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def _innermost(program: list) -> list:
    """Host time cut into ``(start, end, name)`` pieces, each named by the
    innermost span that holds it; time outside every span is left out."""
    out: list = []
    stack: list = []                      # [name, end], outermost first
    cur = 0.0

    def upto(x):
        nonlocal cur
        if stack and x > cur:
            out.append((cur, x, stack[-1][0]))
        cur = max(cur, x)

    for name, s, d, _ in program:
        while stack and stack[-1][1] <= s:
            upto(stack[-1][1])
            stack.pop()
        upto(s)
        stack.append([name, s + d])
    while stack:
        upto(stack[-1][1])
        stack.pop()
    return out


def idle_by_span(t: dict) -> dict:
    """Seconds of the traced window with no op on the device, by the
    innermost ``repro.*`` span the host was in; idle time outside every
    program span (the harness's, or the runtime's) is left out."""
    lo, hi = trace_mod.window(t)
    gaps = _device_gaps(t, lo, hi)
    out: dict[str, float] = {}
    i = 0
    for a, b, name in _innermost(t["program"]):
        a, b = max(a, lo), min(b, hi)
        while i < len(gaps) and gaps[i][1] <= a:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < b:
            ov = min(b, gaps[j][1]) - max(a, gaps[j][0])
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov * 1e-9
            j += 1
    return out


def host_idle_share(t: dict) -> float:
    """Share of the traced window (%) in which the device ran no op while
    the host was inside a program span other than a ``*.wait``: the idle
    the program's own host work causes."""
    lo, hi = trace_mod.window(t)
    idle = sum(s for name, s in idle_by_span(t).items()
               if not name.endswith(WAIT))
    return 100.0 * idle / ((hi - lo) * 1e-9)


def decode_turnarounds_ms(t: dict) -> list[float]:
    """For each two decode dispatches in a row, both inside the traced
    window and with no ``repro.prefill`` starting between them: the host
    time (ms) from the end of the first chunk's read-back to the start of
    the next dispatch, 0 where the next dispatch came first. Dispatch n
    pairs with read-back n in the order both were made."""
    lo, hi = trace_mod.window(t)
    prog = t["program"]
    disp = [s for n, s, d, _ in prog if n == "repro.decode.dispatch"]
    back = [s + d for n, s, d, _ in prog if n == "repro.decode.readback"]
    if disp:
        back = back[next((i for i, e in enumerate(back) if e > disp[0]),
                         len(back)):]
    prefills = [s for n, s, d, _ in prog if n == "repro.prefill"]
    out = []
    for n in range(min(len(disp) - 1, len(back))):
        a, b = disp[n], disp[n + 1]
        if a < lo or b > hi or any(a < p < b for p in prefills):
            continue
        out.append(max(0.0, b - back[n]) * 1e-6)
    return out


def decode_turnaround_ms(t: dict) -> float | None:
    """The median of ``decode_turnarounds_ms``; None with no pair."""
    pairs = decode_turnarounds_ms(t)
    return statistics.median(pairs) if pairs else None


def traced(run) -> dict | None:
    """The loaded trace of a ``--trace 1`` run, or None where the run was
    not traced or the program marks no spans."""
    return load() if run.trace is not None else None
