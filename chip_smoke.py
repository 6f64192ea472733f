"""Serve llama3.2-3b at its published widths on a TPU, through the normal path.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip `MESH local` pass

One chip: builds an Imagefile (`ARCH llama3.2-3b`, bf16, `MESH local`) and
calls `repro.launch.serve.main` in this process, as a user would, three times
over one seeded trace of 32 requests (prompts of 512..1024 tokens, up to 128
generated, 16 slots, one replica):

  1. paged KV (`--paged`): page pool + the Pallas paged-attention kernel;
  2. paged with `--prefix-cache --shared-prefix 256`: requests after the
     first prefill only their suffix, reading the shared pages from the pool;
  3. contiguous KV (no `--paged`): the XLA attention path, reported against
     pass 1 as a share of agreeing tokens (bf16 greedy decoding over random
     weights may rightly diverge, so the share does not gate).

Before that, the paged kernel alone is checked against `paged_attention_ref`
at the model's widths. `--chips 4` runs only pass 1 on the four-chip local
mesh and the same trace on a one-chip mesh built here, and compares them
token for token.

It fails unless every request completes its full budget with tokens in
`[0, vocab_size)`, the compiled paged decode step holds the Pallas kernel
(`tpu_custom_call`), and the kernel agrees with its reference. It refuses to
run without a TPU. The last line of its output is one JSON object naming
the device.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
IMAGEFILE = """FROM scratch
ARCH llama3.2-3b
MESH local
PRECISION compute=bfloat16 params=bfloat16
"""
# the served trace: 16 slots, 32 requests of 512..1024 prompt tokens, budgets
# of up to 128 generated tokens, one pod of one replica
TRACE = ["--replicas", "1", "--slots", "16", "--requests", "32",
         "--prompt-len", "1024", "--gen", "128"]
# weights, the trace and the kernel check's inputs are drawn from this seed
SEED = 0
# the paged kernel against its reference, both in bf16 with f32 softmax
KERNEL_ATOL = KERNEL_RTOL = 2e-2


class SmokeFailure(Exception):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


@contextlib.contextmanager
def recorded_compiles():
    """Record every serve step the path compiles (kind, shapes, executable,
    seconds) without changing what it does."""
    from repro.core.container import Container
    seen: list[dict] = []
    orig = Container.compile_serve_step

    def record(self, kind, **shapes):
        t0 = time.perf_counter()
        exe = orig(self, kind, **shapes)
        seen.append(dict(container=self, kind=kind, shapes=shapes, exe=exe,
                         seconds=time.perf_counter() - t0))
        return exe

    Container.compile_serve_step = record
    try:
        yield seen
    finally:
        Container.compile_serve_step = orig


@contextlib.contextmanager
def one_chip_mesh():
    """`MESH local` resolved to the first chip only: the reference the
    four-chip pass is compared with."""
    import jax
    from repro.core import container
    mesh = jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    orig = container.make_platform_mesh
    container.make_platform_mesh = lambda platform="local": mesh
    try:
        yield
    finally:
        container.make_platform_mesh = orig


def tree_bytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def serve_pass(name: str, imagefile: Path, extra: list[str],
               vocab: int) -> dict:
    """One call of the serving entry point; checks what came back."""
    import jax
    from repro.launch.serve import _tail_budgets, main as serve_main
    argv = ["--image", str(imagefile), "--root", str(ROOT / ".stevedore"),
            "--seed", str(SEED), *TRACE, *extra]
    log(f"{name}: serve {' '.join(argv)}")
    t0 = time.perf_counter()
    with recorded_compiles() as compiles:
        out = serve_main(argv)
    wall = time.perf_counter() - t0

    toks = out["request_tokens"]
    n = int(TRACE[TRACE.index("--requests") + 1])
    budgets = _tail_budgets(int(TRACE[TRACE.index("--gen") + 1]), n)
    require(sorted(toks) == list(range(n)),
            f"{name}: {len(toks)} of {n} requests completed")
    short = [rid for rid, t in toks.items() if len(t) != budgets[rid]]
    require(not short, f"{name}: requests {short} missed their token budget")
    bad = [rid for rid, t in toks.items()
           if any(not 0 <= x < vocab for x in t)]
    require(not bad, f"{name}: requests {bad} hold tokens outside the vocab")

    cache = compiles[0]["container"].compile_cache if compiles else None
    stats = cache.stats if cache else None
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    compile_s = sum(c["seconds"] for c in compiles)
    log(f"{name}: {out['requests']} requests, {out['tokens']} tokens; "
        f"wall {wall:.3f} s (serving {out['wall_s']:.3f} s, compiling "
        f"{compile_s:.3f} s over {len(compiles)} steps)")
    if stats:
        log(f"{name}: CompileCache at {cache.root}: {stats.hits_l1} L1 hits, "
            f"{stats.hits_l2} L2 hits, {stats.misses} misses")
    log(f"{name}: peak_bytes_in_use {mem.get('peak_bytes_in_use')} "
        f"bytes_in_use {mem.get('bytes_in_use')}")
    return dict(out=out, compiles=compiles)


def paged_decode(res: dict) -> dict:
    decodes = [c for c in res["compiles"]
               if c["kind"] in ("decode_chunk_paged", "decode_slots_paged")]
    require(bool(decodes), "no paged decode step was compiled")
    return decodes[0]


def check_paged(name: str, res: dict) -> None:
    """The paged decode step holds the Pallas kernel, not the XLA oracle."""
    dec = paged_decode(res)
    c, shapes = dec["container"], dec["shapes"]
    require("tpu_custom_call" in dec["exe"].as_text(),
            f"{name}: the compiled paged decode step holds no tpu_custom_call")
    pool = c.paged_cache_specs(shapes["n_pages"], shapes["page_size"])
    log(f"{name}: paged decode ({dec['kind']}, {shapes}) holds "
        f"tpu_custom_call; params {tree_bytes(c.abstract_params())} bytes, "
        f"pool {tree_bytes(pool)} bytes")


def agreement(a: dict, b: dict) -> tuple[float, int]:
    """Share of positions where two runs of one trace chose the same token,
    and the number of requests identical end to end."""
    same = total = whole = 0
    for rid, ta in a.items():
        tb = b[rid]
        same += sum(x == y for x, y in zip(ta, tb))
        total += max(len(ta), len(tb))
        whole += ta == tb
    return same / total, whole


def kernel_check(cfg) -> None:
    """The paged-attention entry point, at the model's widths on this chip,
    runs the Pallas kernel and agrees with the pure-jnp reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref
    B, ps, mp = 16, 16, 145
    n_pages = B * mp + 1
    n_kv, hq, hd = cfg.n_kv_heads, cfg.n_heads, cfg.head_dim_
    rng = np.random.default_rng(SEED)
    # every slot owns distinct pages in a shuffled order (page 0 is the
    # pool's garbage page); lengths cover one token, page edges and a full
    # table
    table = (1 + rng.permutation(n_pages - 1)[:B * mp]).reshape(B, mp)
    lengths = rng.integers(1, mp * ps + 1, B)
    lengths[:4] = [1, ps, ps + 1, mp * ps]
    kq, kk, kv = jax.random.split(jax.random.key(SEED), 3)
    q = jax.random.normal(kq, (B, hq, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (n_kv, n_pages, ps, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (n_kv, n_pages, ps, hd), jnp.bfloat16)
    args = (q, k, v, jnp.asarray(table, jnp.int32),
            jnp.asarray(lengths, jnp.int32))
    compiled = jax.jit(paged_attention).lower(*args).compile()
    require("tpu_custom_call" in compiled.as_text(),
            "paged_attention took no Pallas kernel on the chip")
    out = np.asarray(compiled(*args), np.float32)
    ref = np.asarray(jax.jit(paged_attention_ref)(*args), np.float32)
    err = float(np.max(np.abs(out - ref)))
    ok = bool(np.all(np.abs(out - ref)
                     <= KERNEL_ATOL + KERNEL_RTOL * np.abs(ref)))
    log(f"kernel: paged_attention at B={B} Hq={hq} n_kv={n_kv} hd={hd} "
        f"pages={n_pages}x{ps} in bf16: tpu_custom_call, max |kernel - ref| "
        f"{err} (atol {KERNEL_ATOL}, rtol {KERNEL_RTOL})")
    require(ok, f"paged kernel disagrees with paged_attention_ref: max {err}")


def run_one_chip(imagefile: Path, cfg) -> None:
    kernel_check(cfg)
    vocab = cfg.vocab_size
    paged = serve_pass("paged", imagefile, ["--paged"], vocab)
    check_paged("paged", paged)
    paged_toks = paged["out"]["request_tokens"]
    del paged
    gc.collect()

    prefix = serve_pass("prefix", imagefile,
                        ["--prefix-cache", "--shared-prefix", "256"], vocab)
    check_paged("prefix", prefix)
    pc = prefix["out"]["prefix_cache"]
    suffix = [c for c in prefix["compiles"]
              if c["kind"] == "prefill_slot_paged"
              and c["shapes"].get("prefix_len")]
    log(f"prefix: {pc['hits']} hits / {pc['misses']} misses, "
        f"{pc['tokens_saved']} prefill tokens skipped; suffix prefills "
        f"compiled: {[c['shapes'] for c in suffix]}")
    require(pc["hits"] > 0 and suffix,
            "prefix: no suffix prefill read the shared pages")
    del prefix
    gc.collect()

    contiguous = serve_pass("contiguous", imagefile, [], vocab)
    share, whole = agreement(paged_toks, contiguous["out"]["request_tokens"])
    log(f"paged vs contiguous: {share} of tokens agree, {whole} of "
        f"{len(paged_toks)} requests identical (reported, not gated)")


def run_four_chips(imagefile: Path, cfg) -> None:
    import jax
    require(len(jax.devices()) == 4,
            f"--chips 4 needs four chips, JAX sees {len(jax.devices())}")
    vocab = cfg.vocab_size
    four = serve_pass("paged x4", imagefile, ["--paged"], vocab)
    check_paged("paged x4", four)
    require(paged_decode(four)["container"].mesh.size == 4,
            "the four-chip pass did not run on a four-chip mesh")
    four_toks = four["out"]["request_tokens"]
    del four
    gc.collect()
    with one_chip_mesh():
        one = serve_pass("paged x1", imagefile, ["--paged"], vocab)
    require(paged_decode(one)["container"].mesh.size == 1,
            "the reference pass did not run on one chip")
    share, whole = agreement(four_toks, one["out"]["request_tokens"])
    log(f"four chips vs one chip: {share} of tokens agree, {whole} of "
        f"{len(four_toks)} requests identical")
    first = sum(four_toks[r][0] == t[0]
                for r, t in one["out"]["request_tokens"].items())
    require(first == len(four_toks),
            f"four chips vs one chip: only {first} first tokens agree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="serve llama3.2-3b at published widths on a TPU")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the paged pass on the four-chip local mesh, "
                         "compared with the same trace on one chip")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    from repro.core.compile_cache import resolve_cache_dir

    log(f"device_kind {dev.device_kind!r}, {len(jax.devices())} devices, "
        f"jax {jax.__version__}; compile cache {resolve_cache_dir()}")
    run_dir = ROOT / ".stevedore" / "chip_smoke"
    run_dir.mkdir(parents=True, exist_ok=True)
    imagefile = run_dir / "Imagefile"
    imagefile.write_text(IMAGEFILE)
    cfg = get_config("llama3.2-3b")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(imagefile, cfg)
        else:
            run_one_chip(imagefile, cfg)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
