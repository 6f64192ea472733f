"""Paged-attention decode Pallas TPU kernel.

One decode tick: each batch row is an independent request slot whose KV
history lives in non-contiguous *pages* of a global pool. The kernel
gathers the pages at attention time through the page table instead of ever
materialising a contiguous per-slot cache -- the block-allocation idea
(vLLM-style PagedAttention) expressed in the repo's kernel idiom.

Schedule:

* grid = (B,): one grid step per slot, all kv heads at once. Inside it a
  loop walks only the slot's live *blocks* of ``ppb`` consecutive table
  entries, from the block holding the first live position (0, or the
  window's start) to the block holding the last (``lengths[b] - 1``).
  The online-softmax state (m, l, acc) lives in VMEM scratch across the
  loop, per (kv head, q head of its group).
* K and V stay in HBM (``memory_space=pl.ANY``). A block's pages are
  gathered with one ``make_async_copy`` per live page and per pool (all
  kv heads of the page in one strided copy), page ids read from the
  scalar-prefetched table, into one half of a double-buffered VMEM
  scratch ``(2, n_kv, ppb * page_size, hd)``.
* Prefetch order: before a block is computed, the copies of the next live
  block are started into the other half -- the same slot's next block, or
  else the next slot's first block -- so the DMA of block n+1 runs under
  the compute of block n, across grid steps too (the grid is sequential,
  the half in use is carried in SMEM). The first grid step starts slot 0's
  first block.
* Block size: ``ppb`` comes from the operands' shapes alone
  (:func:`pages_per_block`): the largest power of two whose K+V block, as
  laid out in VMEM (``hd`` padded to 128 lanes), fits ``BLOCK_BYTES``,
  and no wider than the table. The table needs no padding: pages at or
  past its width are never live.
* Dead work: a page past ``ceil(length / page_size)`` (or wholly before
  the window) issues no copy; a block with no live page is never visited.
  A parked slot (length 1..4) costs one block: one page copied per pool
  and one block of compute. The dead rows of a live block keep whatever a
  previous block left there (V is zeroed once per call, so they are
  finite) and are masked out of the scores exactly like the positions
  past ``length`` inside the last live page.
* GQA: q enters as ``(n_kv, g, hd)`` per slot; q.k and p.v are batched
  contractions over the kv heads, so kv pages are fetched once per kv
  head, never replicated per q head. Operands enter the MXU in their own
  dtype (bf16 pools: bf16 x bf16 products, exact in f32), accumulating in
  f32; the running max, denominator and accumulator are f32.

The CPU tests run interpret=True, where the copies and semaphores are
emulated and tiling does not apply; tests/test_chip_compile.py compiles
the kernel for a described v5e, and chip_smoke.py runs it against the
reference on the chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# VMEM for one block of K and V pages (one half of the double buffer). On
# a v5e at the served geometries 0.5 MiB blocks ran 12-65% slower than
# 1-4 MiB ones, which ran within 10% of each other (4 MiB fastest by
# 3-6%); 2 MiB keeps the double buffer at a quarter of the 16 MiB of VMEM
# a kernel is given by default.
BLOCK_BYTES = 2 * 1024 * 1024
LANES = 128


def pages_per_block(n_kv: int, page_size: int, hd: int, itemsize: int,
                    max_pages: int) -> int:
    """Table entries per block: the largest power of two whose K and V
    pages for every kv head, ``hd`` padded to whole lanes, fit
    ``BLOCK_BYTES``; at least one, at most the table's width."""
    page_bytes = 2 * n_kv * page_size * (-(-hd // LANES) * LANES) * itemsize
    ppb = 1
    while 2 * ppb * page_bytes <= BLOCK_BYTES and 2 * ppb <= max_pages:
        ppb *= 2
    return ppb


def _pa_kernel(tbl_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
               k_buf, v_buf, sems, half_ref, m_scr, l_scr, acc_scr, *,
               page_size: int, ppb: int, max_pages: int, n_pages: int,
               lanes: int, window: int, scale: float):
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    bk = ppb * page_size

    def live_pages(s):
        """[first, end) table entries of slot ``s`` holding live keys."""
        length = lens_ref[s]
        end = jnp.minimum((length + page_size - 1) // page_size, max_pages)
        first = 0
        if window:
            first = jnp.maximum(length - window, 0) // page_size
        return first, end

    def live_blocks(s):
        first, end = live_pages(s)
        lo = first // ppb
        return lo, jnp.maximum((end + ppb - 1) // ppb, lo + 1)

    def block_copies(s, i, half, act):
        """Start (or wait for) the copies of block ``i`` of slot ``s`` into
        ``half`` of the buffers: one per live page and pool, all kv heads."""
        first, end = live_pages(s)

        def page(p, carry):
            pid = jnp.clip(tbl_ref[s * max_pages + p], 0, n_pages - 1)
            rows = pl.ds(pl.multiple_of((p - i * ppb) * page_size, page_size),
                         page_size)
            for src, dst, sem in ((k_hbm, k_buf, sems.at[0, half]),
                                  (v_hbm, v_buf, sems.at[1, half])):
                act(pltpu.make_async_copy(
                    src.at[:, pid, :, pl.ds(0, lanes)],
                    dst.at[half, :, rows, pl.ds(0, lanes)], sem))
            return carry

        jax.lax.fori_loop(jnp.maximum(first, i * ppb),
                          jnp.minimum(end, (i + 1) * ppb), page, 0)

    def start(s, i, half):
        block_copies(s, i, half, lambda c: c.start())

    @pl.when(b == 0)
    def _first():
        v_buf[...] = jnp.zeros_like(v_buf)    # dead rows stay finite
        half_ref[0] = 0
        start(0, live_blocks(0)[0], 0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    length = lens_ref[b]
    lo, hi = live_blocks(b)

    def block(i, carry):
        half = half_ref[0]
        nxt = 1 - half

        @pl.when(i + 1 < hi)
        def _same_slot():
            start(b, i + 1, nxt)

        @pl.when((i + 1 == hi) & (b + 1 < n_slots))
        def _next_slot():
            start(b + 1, live_blocks(b + 1)[0], nxt)

        block_copies(b, i, half, lambda c: c.wait())
        k = k_buf[half]                               # (n_kv, bk, hd)
        v = v_buf[half]
        q = q_ref[0]                                  # (n_kv, g, hd)
        dt = jnp.promote_types(q.dtype, k.dtype)
        s = jax.lax.dot_general(
            q.astype(dt), k.astype(dt), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # (n_kv, g, bk)

        cols = i * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = cols < length                          # causal incl. self
        if window:
            mask &= cols > length - 1 - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new)
        pr = jnp.where(mask, pr, 0.0)
        l_scr[...] = l_scr[...] * alpha + pr.sum(axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pr.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        half_ref[0] = nxt
        return carry

    jax.lax.fori_loop(lo, hi, block, 0)
    o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
        o_ref.dtype)


def paged_attention_pallas(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           lengths: jax.Array, *, window: int = 0,
                           scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Hq, hd); k/v_pages: (n_kv, n_pages, page_size, hd);
    page_table: (B, max_pages) int32; lengths: (B,) int32 -> (B, Hq, hd)."""
    n_kv, n_pages, ps, hd = k_pages.shape
    B, Hq, _ = q.shape
    assert Hq % n_kv == 0, (Hq, n_kv)
    g = Hq // n_kv
    mp = page_table.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    ppb = pages_per_block(n_kv, ps, hd, k_pages.dtype.itemsize, mp)

    # a copy moves whole lane tiles: an hd short of a lane multiple is laid
    # out padded to 128 lanes in HBM and in VMEM alike, and the compiler
    # takes only slices of whole tiles (the padding lanes are never
    # computed on). The interpreter has no tiles.
    lanes = hd if interpret else -(-hd // LANES) * LANES
    qg = q.reshape(B, n_kv, g, hd)
    kernel = functools.partial(
        _pa_kernel, page_size=ps, ppb=ppb, max_pages=mp, n_pages=n_pages,
        lanes=lanes, window=window, scale=scale)
    slot = pl.BlockSpec((1, n_kv, g, hd), lambda b, tbl, lens: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[slot,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=slot,
        scratch_shapes=[
            pltpu.VMEM((2, n_kv, ppb * ps, hd), k_pages.dtype),   # K halves
            pltpu.VMEM((2, n_kv, ppb * ps, hd), v_pages.dtype),   # V halves
            pltpu.SemaphoreType.DMA((2, 2)),      # (K/V, half)
            pltpu.SMEM((1,), jnp.int32),          # half holding the block
            pltpu.VMEM((n_kv, g, 1), jnp.float32),    # m (running max)
            pltpu.VMEM((n_kv, g, 1), jnp.float32),    # l (running denom)
            pltpu.VMEM((n_kv, g, hd), jnp.float32),   # acc (numerator)
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv, g, hd), q.dtype),
        # the prefetch crosses grid steps: they must run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(B, Hq, hd)
