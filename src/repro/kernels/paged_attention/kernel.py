"""Paged-attention decode Pallas TPU kernel.

One decode tick: each batch row is an independent request slot whose KV
history lives in non-contiguous *pages* of a global pool. The kernel
gathers the pages at attention time through the page table instead of ever
materialising a contiguous per-slot cache -- the block-allocation idea
(vLLM-style PagedAttention) expressed in the repo's kernel idiom.

Schedule (vs flash_attention/kernel.py):

* grid = (B, n_kv, max_pages) with the PAGE dimension innermost: grid steps
  run sequentially on a TPU core, so VMEM scratch (m, l, acc) carries the
  online-softmax state across a slot's pages exactly like the flash kernel
  carries it across KV blocks.
* the page table and lengths ride in as SCALAR-PREFETCH operands
  (PrefetchScalarGridSpec): BlockSpec index maps read ``tbl[b, p]`` to pick
  which physical page the next grid step DMAs -- the gather happens in the
  pipeline's index computation, so KV pages stream HBM->VMEM without a
  host-side or XLA-side copy into contiguous form.
* pages past a slot's length are skipped with ``pl.when`` (no MXU work).
  Their blocks still resolve to a valid page id (unmapped entries point at
  the pool's garbage page 0), so the prefetched DMA stays in bounds; a
  production follow-up could fold the skip into the index map to also
  elide the DMA.
* GQA: the q block is the (group, head_dim) tile of one kv head; kv pages
  are fetched once per kv head, never replicated per q head.

Tiling note: the q tile's sublane dim is the GQA group size (often < 8) --
sub-tile, but the v5e compiler accepts it (tests/test_chip_compile.py) and
chip_smoke.py runs it against the reference on the chip; the CPU tests run
interpret=True, where tiling does not apply.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pa_kernel(tbl_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
               m_scr, l_scr, acc_scr, *,
               page_size: int, window: int, scale: float, n_page_blocks: int):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[b]                  # valid kv positions for this slot
    k_lo = p * page_size
    live = k_lo < length
    if window:
        live &= (k_lo + page_size - 1) > length - 1 - window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (g, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (page_size, hd)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (g, page_size)

        cols = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = cols < length                          # causal incl. self
        if window:
            mask &= cols > length - 1 - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new)
        pr = jnp.where(mask, pr, 0.0)
        l_scr[...] = l_scr[...] * alpha + pr.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pr.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(p == n_page_blocks - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


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

    qg = q.reshape(B, n_kv, g, hd)
    kernel = functools.partial(
        _pa_kernel, page_size=ps, window=window, scale=scale,
        n_page_blocks=mp)

    # index maps see the scalar-prefetch refs as trailing args: the page id
    # for grid step (b, h, p) is read straight out of the table; clamping
    # keeps even hostile tables in bounds (unmapped entries are already 0)
    def kv_map(b, h, p, tbl, lens):
        return (h, jnp.clip(tbl[b, p], 0, n_pages - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_kv, mp),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b, h, p, tbl, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd), kv_map),
            pl.BlockSpec((1, 1, ps, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda b, h, p, tbl, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),      # m (running max)
            pltpu.VMEM((g, 1), jnp.float32),      # l (running denom)
            pltpu.VMEM((g, hd), jnp.float32),     # acc (numerator)
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv, g, hd), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(B, Hq, hd)
