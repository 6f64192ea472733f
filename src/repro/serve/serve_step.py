"""Serving steps: batched prefill, single-token decode, and the
slot-granular variants that power the orchestrator's continuous batching.

``prefill``      : (params, tokens[, frontend_embeds]) -> (last_logits, cache)
``decode``       : (params, cache, tokens (B,1), idx)  -> (logits, new_cache)
``prefill_slot`` : (params, tokens (B,P), length[, frontend_embeds, fe_len])
                                                -> (first_tokens (B,), cache)
``decode_slots`` : (params, cache, tokens (B,1), pos (B,))
                                                -> (next_tokens (B,), cache)

Frontend-embedding archs (musicgen / internvl2) prepend a per-request
modality prefix: ``prefill_slot`` built with ``frontend_len=F`` takes an
(B, F, d_model) embedding buffer plus the per-row count of real prefix rows
and packs [prefix, prompt] contiguously, so the KV cache covers
prefix+prompt and decode proceeds at absolute positions fe_len+len+t with
no further frontend involvement.

The slot variants treat the batch dimension as a bank of independent
*KV-cache slots*: each row is one in-flight request at its own depth
(``pos`` per row), so requests of different lengths decode in lockstep and
a finished slot can be refilled without touching its neighbours.

Every model call inside a step runs under ``jax.named_scope("prefill")``
or ``("decode")``, so each compiled op names its phase in its ``op_name``
metadata, whatever the jitted step is called.

The ``*_paged`` variants replace the contiguous per-slot slabs with a
global page pool + per-slot page table (kernels/paged_attention): same
token-for-token semantics, but slots share KV memory at page granularity
so admission is bounded by pool pressure, not per-slot ``max_len`` slabs.

Sampling masks physically-padded vocab columns (models pad the vocab to a
lane/TP multiple -- see models/layers.padded_vocab) so padded ids can never
be emitted.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.dist.sharding import ShardingRules
from repro.models.transformer import Model


# dispatch classes for compile accounting (Container.compile_serve_step
# buckets cache hits/misses per class; SlotEngine.status surfaces them):
# prefill executables are per-bucket and dominate compile count, decode
# executables are per-geometry and dominate steady-state dispatch
PREFILL_STEPS = frozenset({"prefill", "prefill_slot", "prefill_slot_paged"})
DECODE_STEPS = frozenset({"decode", "decode_slots", "decode_chunk",
                          "decode_slots_paged", "decode_chunk_paged"})


def dispatch_class(kind: str) -> str:
    """\"prefill\" | \"decode\" | \"other\" for a serve-step kind."""
    if kind in PREFILL_STEPS:
        return "prefill"
    if kind in DECODE_STEPS:
        return "decode"
    return "other"


def greedy_sample(logits: jax.Array, vocab_size: int) -> jax.Array:
    vp = logits.shape[-1]
    if vp != vocab_size:
        col = jnp.arange(vp) >= vocab_size
        logits = jnp.where(col, -jnp.inf, logits)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@dataclass
class ServeStepBuilder:
    model: Model
    mesh: Mesh
    rules: ShardingRules

    def build_prefill(self, cache_len: int) -> Callable:
        def prefill(params, tokens, frontend_embeds=None):
            with jax.named_scope("prefill"):
                logits, cache, _ = self.model.forward(
                    params, tokens, frontend_embeds=frontend_embeds,
                    collect_cache=True, cache_len=cache_len)
            return logits[:, -1], cache

        return prefill

    def build_decode(self) -> Callable:
        def decode(params, cache, tokens, idx):
            with jax.named_scope("decode"):
                logits, new_cache = self.model.decode_step(
                    params, cache, tokens, idx)
            return logits[:, -1], new_cache

        return decode

    def build_prefill_slot(self, cache_len: int,
                           frontend_len: int = 0) -> Callable:
        """Prefill request rows whose prompts are right-padded to a bucket.

        tokens: (B, P_bucket); length: int32 count of real tokens -- a
        scalar for the orchestrator's one-request-per-prefill path (B=1) or
        a (B,) vector for the static driver's wave prefill.
        Returns (first_token (B,), cache padded to ``cache_len``).

        With ``frontend_len`` > 0 the signature gains
        ``(frontend_embeds (B, F, D), fe_len)``: a modality prefix consumed
        AHEAD of the token prompt (packed contiguously by Model.forward, so
        tokens sit at positions fe_len..fe_len+length-1 and the first token
        is sampled at position fe_len+length-1).

        Right padding is causally safe for full attention: pad-position K/V
        land at positions >= the real content, which the causal mask hides
        until the decode loop overwrites them in place. (Ring-buffer and
        recurrent caches are NOT pad-safe -- callers use exact-length
        buckets there; see orchestrator.scheduler.SlotEngine.)
        """
        vocab = self.model.cfg.vocab_size

        def _sample_at(logits, last_pos):
            last = jnp.take_along_axis(
                logits, last_pos.reshape(-1, 1, 1), axis=1)[:, 0]
            return greedy_sample(last, vocab)

        if frontend_len:
            def prefill_slot(params, tokens, length, frontend_embeds, fe_len):
                with jax.named_scope("prefill"):
                    logits, cache, _ = self.model.forward(
                        params, tokens, frontend_embeds=frontend_embeds,
                        frontend_len=fe_len, collect_cache=True,
                        cache_len=cache_len)
                return _sample_at(logits,
                                  jnp.asarray(fe_len + length - 1)), cache

            return prefill_slot

        def prefill_slot(params, tokens, length):
            with jax.named_scope("prefill"):
                logits, cache, _ = self.model.forward(
                    params, tokens, collect_cache=True, cache_len=cache_len)
            return _sample_at(logits, jnp.asarray(length - 1)), cache

        return prefill_slot

    def build_decode_slots(self) -> Callable:
        """One decode tick over a slot bank: every row advances by one token
        at its own position. Free slots decode garbage into their own rows,
        which the next insertion overwrites -- no masking needed in-kernel.
        """
        decode = self.build_decode()
        vocab = self.model.cfg.vocab_size

        def decode_slots(params, cache, tokens, pos):
            logits, new_cache = decode(params, cache, tokens, pos)
            return greedy_sample(logits, vocab), new_cache

        return decode_slots

    def build_decode_chunk(self, n_steps: int) -> Callable:
        """Multi-step slot decode: ``n_steps`` ticks in ONE dispatch.

        Amortizes per-dispatch host overhead (pytree flatten, executable
        call, token sync) over ``n_steps`` decode ticks -- the multi-step
        scheduling trick. Slots that finish mid-chunk keep decoding until
        the chunk boundary; the host discards their surplus tokens (bounded
        waste of ``n_steps - 1`` positions, accounted by the scheduler).

        (params, cache, tokens (B,1), pos (B,)) ->
            (toks (B, n_steps), next_tokens (B,1), pos+n_steps, cache)
        """
        decode = self.build_decode()
        vocab = self.model.cfg.vocab_size

        def decode_chunk(params, cache, tokens, pos):
            def body(carry, _):
                cache, tok, pos = carry
                logits, cache = decode(params, cache, tok, pos)
                nxt = greedy_sample(logits, vocab)[:, None]
                return (cache, nxt, pos + 1), nxt[:, 0]

            (cache, tok, pos), toks = jax.lax.scan(
                body, (cache, tokens, pos), None, length=n_steps)
            return jnp.moveaxis(toks, 0, 1), tok, pos, cache

        return decode_chunk

    # -- paged variants (KV in a global page pool; see kernels/paged_attention
    # and orchestrator/page_pool.py) ----------------------------------------

    def build_prefill_slot_paged(self, prompt_len: int, page_size: int,
                                 frontend_len: int = 0,
                                 prefix_len: int = 0) -> Callable:
        """prefill_slot whose cache comes back PAGE-MAJOR, ready to scatter
        into the pool: each attention entry is (count, n_kv, n_prompt_pages,
        page_size, hd) with n_prompt_pages = ceil((frontend_len +
        prompt_len) / page_size) -- the frontend prefix occupies the leading
        cache positions, exactly as in the contiguous layout. The host
        writes row j of that tree into physical page ``table[slot, j]`` (one
        jitted scatter -- see scheduler). Padding rows beyond the true
        content carry right-pad garbage; the paged mask hides everything
        past the written positions until decode overwrites it.

        With ``prefix_len`` > 0 (prefix-registry hit) this becomes the
        SUFFIX prefill: ``tokens`` are only the uncached tail of the prompt
        (bucketed to ``prompt_len``), the signature gains the live page
        pool plus the (ceil(prefix_len / page_size),) physical page ids of
        the matched prefix chain, and query positions are offset past the
        prefix. ``prefix_len`` may end MID-page (a radix partial match):
        the boundary page -- the last ``prefix_pages`` entry -- is then a
        read-only MERGE OPERAND: its first ``prefix_len % page_size``
        positions are copied ahead of the suffix KV so the returned
        page-major cache starts page-aligned, and the host scatters it into
        the slot's private rows starting AFTER the fully-shared rows (the
        boundary page itself stays shared property of the registry)."""
        if prefix_len:
            if frontend_len:
                raise NotImplementedError(
                    "prefix-cached suffix prefill does not compose with "
                    "frontend embeddings")
            span = prompt_len                  # the suffix bucket
            vocab = self.model.cfg.vocab_size
            frac = prefix_len % page_size      # front-partial merge width
            np_ = -(-(frac + span) // page_size)
            pad = np_ * page_size - (frac + span)

            def prefill_suffix_paged(params, pool, tokens, length,
                                     prefix_pages):
                with jax.named_scope("prefill"):
                    logits, cache, _ = self.model.forward(
                        params, tokens, collect_cache=True, cache_len=span,
                        prefix_kv=pool, prefix_pages=prefix_pages,
                        prefix_len=prefix_len)
                last = jnp.take_along_axis(
                    logits, jnp.asarray(length - 1).reshape(-1, 1, 1),
                    axis=1)[:, 0]
                first = greedy_sample(last, vocab)

                def to_pages(e, pl):
                    # e: (count, 1, S, n_kv, hd) suffix cache;
                    # pl: (count, n_kv, n_pages, ps, hd) live pool leaf
                    e = e[:, 0]
                    if frac:
                        # front-partial merge: the shared boundary page's
                        # first ``frac`` positions lead the slot's first
                        # private page (KV there depends only on identical
                        # preceding tokens, so the copy is sound)
                        bp = jnp.take(pl, prefix_pages[-1], axis=2)
                        bp = bp[:, :, :frac].transpose(0, 2, 1, 3)
                        e = jnp.concatenate([bp.astype(e.dtype), e], axis=1)
                    if pad:
                        e = jnp.pad(e, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    cnt, _, n_kv, hd = e.shape
                    e = e.reshape(cnt, np_, page_size, n_kv, hd)
                    return e.transpose(0, 3, 1, 2, 4)

                return first, jax.tree.map(to_pages, cache, pool)

            return prefill_suffix_paged

        span = prompt_len + frontend_len
        inner = self.build_prefill_slot(span, frontend_len)
        np_ = -(-span // page_size)
        pad = np_ * page_size - span

        def prefill_slot_paged(params, tokens, length, *fe_args):
            first, cache = inner(params, tokens, length, *fe_args)

            def to_pages(e):
                # (count, 1, S, n_kv, hd) -> (count, n_kv, np_, ps, hd)
                e = e[:, 0]
                if pad:
                    e = jnp.pad(e, ((0, 0), (0, pad), (0, 0), (0, 0)))
                cnt, _, n_kv, hd = e.shape
                e = e.reshape(cnt, np_, page_size, n_kv, hd)
                return e.transpose(0, 3, 1, 2, 4)

            return first, jax.tree.map(to_pages, cache)

        return prefill_slot_paged

    def build_decode_slots_paged(self) -> Callable:
        """One decode tick over the slot bank with paged KV: identical
        semantics to decode_slots plus the (B, max_pages) page table."""
        vocab = self.model.cfg.vocab_size

        def decode_slots_paged(params, cache, tokens, pos, page_table):
            with jax.named_scope("decode"):
                logits, new_cache = self.model.decode_step(
                    params, cache, tokens, pos, page_table=page_table)
            return greedy_sample(logits[:, -1], vocab), new_cache

        return decode_slots_paged

    def build_decode_chunk_paged(self, n_steps: int) -> Callable:
        """Multi-step paged slot decode. The page table is FIXED for the
        whole chunk: the scheduler pre-allocates pages covering every write
        position pos..pos+n_steps-1 before dispatch (alloc-on-write happens
        host-side, bounded one chunk ahead)."""
        vocab = self.model.cfg.vocab_size

        def decode_chunk_paged(params, cache, tokens, pos, page_table):
            def body(carry, _):
                cache, tok, pos = carry
                with jax.named_scope("decode"):
                    logits, cache = self.model.decode_step(
                        params, cache, tok, pos, page_table=page_table)
                nxt = greedy_sample(logits[:, -1], vocab)[:, None]
                return (cache, nxt, pos + 1), nxt[:, 0]

            (cache, tok, pos), toks = jax.lax.scan(
                body, (cache, tokens, pos), None, length=n_steps)
            return jnp.moveaxis(toks, 0, 1), tok, pos, cache

        return decode_chunk_paged

    def build_generate_loop(self, n_steps: int) -> Callable:
        """Greedy autoregressive loop (used by examples + integration tests)."""
        decode = self.build_decode()
        vocab = self.model.cfg.vocab_size

        def generate(params, cache, first_token, start_idx):
            def body(carry, _):
                cache, tok, idx = carry
                logits, cache = decode(params, cache, tok, idx)
                nxt = greedy_sample(logits, vocab)[:, None]
                return (cache, nxt, idx + 1), nxt[:, 0]

            (cache, _, _), toks = jax.lax.scan(
                body, (cache, first_token, start_idx), None, length=n_steps)
            return jnp.moveaxis(toks, 0, 1), cache   # (B, n_steps)

        return generate
