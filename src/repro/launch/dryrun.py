import os
# a CPU-only analysis by design: 512 virtual host devices, never the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: for the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh,
``jax.jit(step).lower(**input_specs).compile()`` must succeed for every
assigned architecture x input-shape cell, and the compiled artifact yields
memory_analysis (fits?) + cost_analysis (FLOPs/bytes) + the collective
schedule for EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun \
      --arch all --shape all --mesh pod,multipod --out results/dryrun

Per-cell JSON artifacts land under --out; rerunning skips cells whose
artifact already exists (crash-resumable, like any decent launcher).
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCH_IDS, get_config
from repro.core.container import Container
from repro.core.image import ImageBuilder
from repro.launch.analysis import (
    HBM_PER_CHIP, Cost, cost_of, model_flops, parse_collectives, roofline,
)
from repro.models.config import SHAPE_CELLS, get_shape_cell, long_context_capable

MESH_PLATFORMS = {"pod": "pod", "multipod": "multipod"}


def build_image(arch: str, shape: str, platform: str, *,
                collectives: str = "generic", settings: dict | None = None,
                precision: dict | None = None,
                arch_overrides: dict | None = None,
                collective_options: dict | None = None):
    b = (ImageBuilder.from_scratch()
         .arch(arch, **(arch_overrides or {}))
         .shape(shape)
         .mesh(platform)
         .precision(**(precision or
                       {"params": "float32", "compute": "bfloat16"}))
         .collectives(collectives, **(collective_options or {})))
    if settings:
        b.set(**settings)
    return b.build()


def skip_reason(arch: str, shape: str) -> str | None:
    cfg = get_config(arch)
    if shape == "long_500k" and not long_context_capable(cfg):
        return ("pure full-attention arch: 512k cached decode is quadratic-"
                "cost; cell skipped per assignment (DESIGN.md §4)")
    return None


def run_cell(arch: str, shape: str, platform: str, *,
             collectives: str = "generic", settings: dict | None = None,
             precision: dict | None = None,
             arch_overrides: dict | None = None,
             collective_options: dict | None = None,
             probes: bool = True) -> dict:
    """Lower+compile one cell; returns the result record."""
    t_start = time.perf_counter()
    image = build_image(arch, shape, platform,
                        collectives=collectives, settings=settings,
                        precision=precision, arch_overrides=arch_overrides,
                        collective_options=collective_options)
    c = Container(image, platform=platform)
    kind = c.cell.kind

    lowered = c.lower_step(kind)
    t_lower = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter()

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    total = cost_of(compiled, hlo)

    stage_counts = [st.count for st in c.model.stages]
    probe_info = []
    if probes:
        for si, st in enumerate(c.model.stages):
            if st.count <= 1:
                probe_info.append({"stage": si, "count": st.count,
                                   "scaled": False})
                continue
            pl, count = c.lower_unit_probe(si, kind)
            pc = pl.compile()
            unit_cost = cost_of(pc)
            total.add(unit_cost, count - 1)
            probe_info.append({
                "stage": si, "count": count, "scaled": True,
                "unit_flops": unit_cost.flops,
                "unit_bytes": unit_cost.bytes_accessed,
                "unit_wire_bytes": unit_cost.collectives.wire_bytes,
            })

    n_dev = c.mesh.devices.size
    mf = model_flops(c.arch, c.cell)
    rl = roofline(total, mf, n_dev)

    args_b = int(mem.argument_size_in_bytes)
    out_b = int(mem.output_size_in_bytes)
    tmp_b = int(mem.temp_size_in_bytes)
    alias_b = int(mem.alias_size_in_bytes)
    resident = args_b + tmp_b + max(0, out_b - alias_b)
    record = {
        "arch": arch, "shape": shape, "mesh": platform, "kind": kind,
        "status": "ok",
        "image": image.digest,
        "abi": collectives,
        "settings": settings or {},
        "precision": precision or {"params": "float32", "compute": "bfloat16"},
        "arch_overrides": arch_overrides or {},
        "n_devices": n_dev,
        "seconds": {"lower": t_lower - t_start,
                    "compile": t_compile - t_lower},
        "memory": {
            "argument_bytes": args_b, "output_bytes": out_b,
            "temp_bytes": tmp_b, "alias_bytes": alias_b,
            "resident_bytes_per_device": resident,
            "hbm_per_chip": HBM_PER_CHIP,
            "fits_hbm": resident <= HBM_PER_CHIP,
        },
        "cost": {
            "flops_per_device": total.flops,
            "bytes_per_device": total.bytes_accessed,
            "collective_bytes_by_op": total.collectives.bytes_by_op,
            "collective_count_by_op": total.collectives.count_by_op,
            "wire_bytes_per_device": total.collectives.wire_bytes,
            "cross_pod_bytes_per_device": total.collectives.cross_pod_bytes,
        },
        "stages": stage_counts,
        "probes": probe_info,
        "roofline": rl.to_dict(),
    }
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="pod,multipod")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--collectives", default="generic")
    ap.add_argument("--settings", default="")
    ap.add_argument("--precision", default="",
                    help='JSON, e.g. {"params":"bfloat16","compute":"bfloat16"}')
    ap.add_argument("--arch-overrides", default="",
                    help='JSON ModelConfig overrides, e.g. {"attn_score_dtype":"bfloat16"}')
    ap.add_argument("--collective-options", default="",
                    help='JSON ABI options, e.g. {"mode":"explicit","zero1":false}')
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="",
                    help="suffix for artifact filenames (perf variants)")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shapes = (list(SHAPE_CELLS) if args.shape == "all"
              else args.shape.split(","))
    meshes = args.mesh.split(",")
    # default: activation checkpointing on (required to fit ANY large train
    # cell; orthogonal to the paper-faithful generic-vs-host ABI axis)
    settings = json.loads(args.settings) if args.settings else {"remat": "dots"}
    precision = json.loads(args.precision) if args.precision else None
    arch_overrides = (json.loads(args.arch_overrides)
                      if args.arch_overrides else None)
    collective_options = (json.loads(args.collective_options)
                          if args.collective_options else None)

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mesh in meshes:
                tag = f"-{args.tag}" if args.tag else ""
                name = f"{arch}__{shape}__{mesh}{tag}.json"
                path = out / name
                if path.exists() and not args.force:
                    print(f"[skip-cached] {name}")
                    continue
                reason = skip_reason(arch, shape)
                if reason:
                    rec = {"arch": arch, "shape": shape, "mesh": mesh,
                           "status": "skipped", "reason": reason}
                    path.write_text(json.dumps(rec, indent=2))
                    n_skip += 1
                    print(f"[skipped]  {name}: {reason[:60]}...")
                    continue
                t0 = time.perf_counter()
                try:
                    rec = run_cell(arch, shape, mesh,
                                   collectives=args.collectives,
                                   settings=settings,
                                   precision=precision,
                                   arch_overrides=arch_overrides,
                                   collective_options=collective_options,
                                   probes=not args.no_probes)
                    path.write_text(json.dumps(rec, indent=2))
                    n_ok += 1
                    rl = rec["roofline"]
                    print(f"[ok {time.perf_counter()-t0:6.1f}s] {name} "
                          f"dom={rl['dominant']:10s} "
                          f"bound={rl['compute_s']:.2e}/{rl['memory_s']:.2e}/"
                          f"{rl['collective_s']:.2e}s "
                          f"mem/dev={rec['memory']['resident_bytes_per_device']/2**30:.2f}GiB")
                except Exception as e:
                    n_fail += 1
                    rec = {"arch": arch, "shape": shape, "mesh": mesh,
                           "status": "failed", "error": str(e),
                           "traceback": traceback.format_exc()}
                    path.write_text(json.dumps(rec, indent=2))
                    print(f"[FAILED {time.perf_counter()-t0:6.1f}s] {name}: "
                          f"{type(e).__name__}: {str(e)[:200]}")
    print(f"\ndone: ok={n_ok} skipped={n_skip} failed={n_fail}")


if __name__ == "__main__":
    main()
