"""Operations and bytes the algorithm needs, from shapes alone.

Counted for the work a request needs, not for how a program carries it
out: padding, dead pages, idle slots and recomputation are never counted,
so every implementation of the same work is held to the same count.
``d`` is ``reference.dense_decoder.dims(config)``.
"""

from __future__ import annotations


def layer_matmul_params(d: dict) -> int:
    """Weights one token multiplies through in one layer."""
    D, H, KV, hd, F = d["d"], d["h"], d["kv"], d["hd"], d["f"]
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def attention_flops(d: dict, context: int) -> int:
    """One query position over ``context`` keys, one layer: q.k and p.v."""
    return 4 * d["h"] * d["hd"] * context


def paged_attention_call(d: dict, contexts, itemsize: int = 2) -> tuple:
    """(flops, bytes) of one decode-attention call of one layer over the
    live contexts of the active slots: every live key and value read once,
    each slot's query read and output written once."""
    flops = sum(attention_flops(d, c) for c in contexts)
    kv = sum(2 * d["kv"] * d["hd"] * c for c in contexts) * itemsize
    qo = 2 * d["h"] * d["hd"] * len(contexts) * itemsize
    return flops, kv + qo


def token_flops(d: dict, context: int, head: bool) -> int:
    """Model FLOPs of one position attending ``context`` keys, all layers;
    ``head`` adds the output projection for a position that samples."""
    per_layer = 2 * layer_matmul_params(d) + attention_flops(d, context)
    return d["layers"] * per_layer + (2 * d["d"] * d["vocab"] if head else 0)


def prefill_flops(d: dict, prompt: int, cached: int = 0) -> int:
    """Prefill of the ``prompt - cached`` uncached positions, each over
    its causal context, sampling at the last."""
    n = prompt - cached
    attn = d["layers"] * 4 * d["h"] * d["hd"] * (
        (cached + 1 + prompt) * n // 2)
    return (n * d["layers"] * 2 * layer_matmul_params(d) + attn
            + 2 * d["d"] * d["vocab"])

