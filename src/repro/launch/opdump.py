import os
# a CPU-only analysis by design: 512 virtual host devices, never the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Per-op byte/flop breakdown of one unit probe -- the dry-run 'profiler'.

  PYTHONPATH=src python -m repro.launch.opdump --arch deepseek-67b \
      --shape train_4k --mesh multipod --stage 0 [--settings '{...}']

Groups RESULT bytes of every HLO instruction in the compiled per-unit probe
by opcode (fusion kinds separated), which is the closest thing to a memory
profile this CPU container can produce: it shows WHERE the roofline memory
term comes from.
"""

import argparse
import json
import re
from collections import defaultdict

from repro.core.container import Container
from repro.launch.analysis import _shape_bytes, parse_collectives
from repro.launch.dryrun import build_image

_INSTR = re.compile(r"^\s+(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(")


def op_breakdown(hlo: str) -> dict[str, float]:
    agg: dict[str, float] = defaultdict(float)
    for line in hlo.splitlines():
        line = line.split(", metadata=")[0]
        m = _INSTR.match(line)
        if not m:
            continue
        typ, op = m.groups()
        agg[op] += _shape_bytes(typ)
    return dict(agg)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="pod")
    ap.add_argument("--stage", type=int, default=0)
    ap.add_argument("--collectives", default="generic")
    ap.add_argument("--settings", default='{"remat":"dots"}')
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()

    image = build_image(args.arch, args.shape, args.mesh,
                        collectives=args.collectives,
                        settings=json.loads(args.settings))
    c = Container(image, platform=args.mesh)
    lowered, count = c.lower_unit_probe(args.stage, c.cell.kind)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    print(f"# unit probe {args.arch}/{args.shape}/{args.mesh} stage{args.stage} "
          f"x{count}")
    print(f"# flops/dev={ca.get('flops', 0):.3e}  "
          f"bytes_accessed/dev={ca.get('bytes accessed', 0):.3e}")
    text = compiled.as_text()
    st = parse_collectives(text)
    print("# collectives (per unit, per device):")
    for op in sorted(st.bytes_by_op, key=lambda o: -st.bytes_by_op[o]):
        print(f"#   {op:20s} n={st.count_by_op[op]:4d} bytes={st.bytes_by_op[op]:.3e}")
    # biggest individual collective instructions
    import re as _re
    biggest = []
    for line in text.splitlines():
        line = line.split(", metadata=")[0]
        mm = _re.search(r"= (\S+) (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)", line)
        if mm:
            biggest.append((_shape_bytes(mm.group(1)), mm.group(2), mm.group(1)[:60]))
    for b, op, t in sorted(biggest, reverse=True)[:8]:
        print(f"#   big: {op:18s} {b:.3e}  {t}")
    agg = op_breakdown(text)
    total = sum(agg.values())
    print(f"# result-bytes total (per unit, per device): {total:.3e}")
    for op, b in sorted(agg.items(), key=lambda kv: -kv[1])[: args.top]:
        print(f"{op:28s} {b:.3e}  {b / total * 100:5.1f}%")


if __name__ == "__main__":
    main()
