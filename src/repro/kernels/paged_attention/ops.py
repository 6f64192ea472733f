"""Public entry point for paged decode attention.

On TPU the Pallas kernel copies each slot's live KV pages into VMEM,
prefetching the next block under the current one; elsewhere (the CPU)
the XLA oracle runs instead -- NOT the interpreted kernel, which would put
an interpreter in the decode hot loop of every serving tick. The oracle
gathers pages into contiguous form inside the jitted step, which XLA
fuses; numerics are identical to ``models.attention._sdpa_dense`` so paged
and contiguous slot decode agree token-for-token
(tests/test_paged_attention.py pins all three against each other).
"""

from __future__ import annotations

import jax

from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ref import paged_attention_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array, lengths: jax.Array,
                    *, window: int = 0,
                    scale: float | None = None) -> jax.Array:
    """q: (B, Hq, hd); k/v_pages: (n_kv, n_pages, page_size, hd);
    page_table: (B, max_pages); lengths: (B,) -> (B, Hq, hd)."""
    if _on_tpu():
        return paged_attention_pallas(q, k_pages, v_pages, page_table,
                                      lengths, window=window, scale=scale)
    return paged_attention_ref(q, k_pages, v_pages, page_table, lengths,
                               window=window, scale=scale)
