"""Find the knee of an open-loop cell: the highest offered rate the system
sustains. One set-up, then one window per rate, each after the mix's
warm-in and drained before the next rate, on the chip the cell asks for:

    python3 bench/sweep.py --workload deepseek67b.chat \
        --rates 1.0 1.2 1.4 1.6 1.8 --seconds 51 --seed 7

Prints one JSON line per rate: the output tokens offered (the answers of
the requests due in the window) and delivered per second, and the time to
first token of the requests due in the window's first and second halves;
where the system falls behind, the second half waits longer. The knee,
once found, is written into the cell's traffic file as a fixed rate (about
4/5 of it); runs of the benchmark never search.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    from bench import run
    run.setup_jax(str(run.CACHE_DIR))
    from bench import spec, stats
    from bench.serve import CompileCounter, Server, run_window
    cell = spec.workload(args.workload)
    if run.devices(cell["chips"], True) is None:
        return 2
    server = Server(cell, args.seed)
    server.warm_up(cell["traffic"])
    compiles = CompileCounter()
    for i, rate in enumerate(args.rates):
        mix = dict(cell["traffic"], rate_per_s=rate)
        w = run_window(server, mix, args.seconds, args.seed + i, compiles)
        e2e = stats.end_to_end(w)
        due = stats.due_in_window(w.records, w.t0, w.t1)
        done = sum(1 for r in due if r.done_at is not None)
        backlog = sum(1 for r in due if not r.times)
        mid = (w.t0 + w.t1) / 2
        halves = [stats.nearest_rank(
            [t for r, t in zip(due, stats.ttfts(due, w.t0, w.t1))
             if (r.due < mid) == first], 95) for first in (True, False)]
        offered = sum(r.req.max_new_tokens for r in due) / (w.t1 - w.t0)
        server.sched.run()                      # drain before the next rate
        print(json.dumps(dict(rate=rate, due=len(due), finished=done,
                              no_first_token=backlog,
                              offered_tok_per_s=offered,
                              ttft_p95_first_half_s=halves[0],
                              ttft_p95_second_half_s=halves[1],
                              compiles=w.compiles, **e2e)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
