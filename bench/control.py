"""Readings that the correctness limits are set from, on the chip.

    python3 bench/control.py --workload deepseek67b.chat --seconds 25 \
        --seeds 11 12 13 ... --control 3

One process, one Pod: for every seed it loads that seed's weights, serves
a short window of the cell's own traffic and keeps the sample of finished
requests a run would check. Once the program is freed it reads, per seed,
the widest logit gap of the served tokens under the float32 reference
(the program's reading, the lower end of the limit) and, for the first
``--control`` seeds, the widest gap of the tokens the fp8 control puts
first at the same positions (the control's reading, the upper end). One
JSON line per seed.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    from bench import run
    run.setup_jax(str(run.CACHE_DIR))
    import numpy as np
    from bench import check, spec
    from bench.serve import CompileCounter, Server, run_window
    cell = spec.workload(args.workload)
    if run.devices(cell["chips"], True) is None:
        return 2
    mix, n = cell["traffic"], int(cell["limits"]["sample_requests"])
    compiles = CompileCounter()
    server = Server(cell, args.seeds[0])
    samples = []
    for i, seed in enumerate(args.seeds):
        if i:
            server.reload(seed)
        server.warm_up(mix)
        w = run_window(server, mix, args.seconds, seed, compiles)
        recs = check.sample(w.records, w.t0, w.t1, n, seed)
        samples.append((seed, check.sequences(recs)))
        run.log(f"seed {seed}: {len(recs)} requests sampled")
    server.release()
    ref = server.ref
    for i, (seed, (seqs, wanted, served)) in enumerate(samples):
        f32 = ref.logits(cell["config"], seed, seqs, wanted, "f32")
        out = {"seed": seed, "requests": len(seqs),
               "tokens": int(sum(len(s) for s in served)),
               "program_gap_max": float(check.gaps(f32, served).max())}
        if i < args.control:
            f8 = ref.logits(cell["config"], seed, seqs, wanted, "fp8")
            ctrl = check.gaps(f32, [lg.argmax(-1) for lg in f8])
            out["control_gap_max"] = float(ctrl.max())
            out["control_gap_p50"] = float(np.median(ctrl))
            out["control_flips"] = int((ctrl > 0).sum())
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
