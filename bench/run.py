"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's Pod (``bench/configs/<config>.json``), loads weights drawn
from the seed, warms up every shape the cell's traffic
(``bench/traffic/<mix>.json``) reaches, then serves that traffic for
``--seconds`` and prints one JSON line: with ``--trace 0`` the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics
(``bench/metrics/<name>.py``), read from a profiler trace of the window's
last seconds and from the program's counters. After the window the served
tokens are checked against the plain reference (``bench/check.py``).

It refuses to run without a TPU, or with fewer chips than the cell asks
for: exit code 2, no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# run as a script, bench/ leads sys.path and its modules (trace, stats)
# would shadow the standard library's: import them as bench.* instead
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
# JAX's persistent cache and the program's CompileCache, at one fixed path
# inside the checkout: only the first run of a cell in a checkout compiles
CACHE_DIR = ROOT / "bench" / ".cache" / "jax"
TRACE_DIR = ROOT / "bench" / ".work" / "trace"
# the traced part of a --trace 1 window: its last seconds
TRACE_SECONDS = 10.0


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def devices(chips: int, require_chip: bool):
    """The chips the cell runs on, or None (with the reason on stderr)
    when this machine does not have them."""
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        log(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind})")
        return None
    return devs[:chips]


def _peak(stats: dict) -> int:
    # a TPU holds an executable's temporaries apart from its buffers:
    # ``bytes_reserved``, outside ``bytes_in_use``
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def memory_peak(devs) -> tuple[int, dict]:
    """Peak bytes on the fullest chip, buffers and the programs'
    temporaries together, and that chip's whole ``memory_stats``."""
    stats = max((d.memory_stats() or {} for d in devs), key=_peak)
    return _peak(stats), stats


def make_tracer(window_state: dict, seconds: float):
    """Starts the profiler TRACE_SECONDS before the window's end and stops
    it at the end; the host's spans inside carry ``bench.*`` names."""
    import jax

    def tracer(now, t_end):
        st = window_state
        if not st.get("on") and not st.get("done") and (
                now >= t_end - min(TRACE_SECONDS, seconds) and now < t_end):
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            st["ann"] = jax.profiler.TraceAnnotation("bench.window")
            st["ann"].__enter__()
            st["on"], st["start"] = True, time.perf_counter()
        elif st.get("on") and now >= t_end:
            st["stop"] = time.perf_counter()
            st["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            st["on"], st["done"] = False, True

    def annotate(name):
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    return tracer, annotate


def per_layer(cell, server, window, trace_state, reduced, peaks, chips):
    from bench.spec import metric_reader
    steps = [s for s in window.steps
             if trace_state and trace_state.get("start", 1e300) <= s.t0
             and s.t1 <= trace_state.get("stop", -1e300)]
    delta = {k: window.counters1[k] - window.counters0[k]
             for k in window.counters0}
    run = SimpleNamespace(cell=cell, dims=server.dims, window=window,
                          delta=delta, trace=reduced, trace_steps=steps,
                          peaks=peaks, chips=chips, chunk=server.chunk,
                          slots=server.slots, saved=server.prefix_saved())
    out = {}
    for m in cell["per_layer"]:
        v = metric_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def setup_jax(cache_dir: str | None = None):
    """JAX with its persistent compilation cache at ``cache_dir`` (the
    checkout's fixed one by default), caching every program however fast
    it compiled, so that only a checkout's first run compiles."""
    cache = Path(cache_dir or os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or CACHE_DIR)
    cache.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def main(argv=None) -> int:
    args = parse(argv)
    setup_jax(str(CACHE_DIR))
    from bench import spec
    return execute(spec.workload(args.workload), args)


def execute(cell: dict, args, require_chip: bool = True,
            peaks: dict | None = None) -> int:
    """One run of ``cell``; prints its result line and returns the exit
    code. ``require_chip=False`` skips the look for a TPU and runs on
    whatever JAX finds, with the given ``peaks`` (the CPU tests)."""
    setup_jax()
    devs = devices(cell["chips"], require_chip)
    if devs is None:
        return 2
    from bench import check, stats
    from bench.peaks import peaks as peak_table
    from bench.serve import CompileCounter, Server, run_window
    if require_chip:
        peaks = peak_table(devs[0].device_kind)

    compiles = CompileCounter()
    t_imported = time.perf_counter()
    server = Server(cell, args.seed)
    n_warm = server.warm_up(cell["traffic"])
    log(f"warm-up: {n_warm} requests; pool {server.n_pages} pages of "
        f"{server.page_size} ({server.pool_bytes} bytes), params "
        f"{server.params_bytes} bytes; set-up phases "
        f"{json.dumps(server.phases)}, imports "
        f"{t_imported - T_START:.3f} s")
    trace_state: dict = {}
    tracer = annotate = None
    if args.trace:
        tracer, annotate = make_tracer(trace_state, args.seconds)
    window = run_window(server, cell["traffic"], args.seconds, args.seed,
                        compiles, tracer=tracer, annotate=annotate)
    setup_s = window.t0 - T_START
    mem, mem_stats = memory_peak(devs)
    log(f"memory_stats of the fullest chip: {json.dumps(mem_stats)}")

    reduced = None
    if args.trace:
        from bench import trace as trace_mod
        from bench.roofline import is_paged_kernel
        reduced = trace_mod.reduce(trace_mod.load(TRACE_DIR),
                                   kernel=is_paged_kernel)
        metrics = per_layer(cell, server, window, trace_state, reduced,
                            peaks, cell["chips"])
    else:
        e2e = stats.end_to_end(window)
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell["end_to_end"] if m["name"] in e2e}
    attempted, failed = stats.attempted_failed(window)
    late = max(window.late) if window.late else 0.0
    log(f"window {window.t1 - window.t0:.3f} s, {len(window.steps)} steps, "
        f"{attempted} requests due, {failed} failed, generator late by at "
        f"most {late:.4f} s, {window.compiles} JAX compiles in the window, "
        f"set-up {setup_s:.3f} s")

    server.release()
    verdict, details = check.run(server.ref, cell["config"], args.seed,
                                 window, cell["limits"])
    log(f"check: {json.dumps(details)}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": verdict["correct"], "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        log(f"idle by host span: {json.dumps(reduced['idle_by_host'])}")
    result["check"] = verdict["numbers"]
    for name, n in verdict["numbers"].items():
        print(f"{name} {n['value']} {n['rule']} {n['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
