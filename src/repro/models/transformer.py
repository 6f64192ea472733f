"""Decoder-only LM assembly for all 10 architectures.

Scan discipline (compile-time critical at 512 devices / 95 layers):
layers are grouped into *stages*; each stage is a stack of identical *units*
scanned with ``jax.lax.scan`` over stacked parameters. A unit is one or more
blocks (recurrentgemma's cycle (rec, rec, attn) is one unit of three blocks);
remainder layers that do not complete a cycle form a trailing stage.

Block kinds: attn (full/local + MLP), moe (attn + routed MoE), ssm (Mamba-2
SSD), rec (RG-LRU + MLP).

The same stage structure drives train (no cache), prefill (collect cache as
scan ys) and decode (cache as scan xs/ys), so cache pytrees always line up
with parameter pytrees.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rec_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import AttnGeometry, resolve_geometry
from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_mlp,
    apply_norm,
    embed_defs,
    embed_tokens,
    lm_logits,
    mlp_defs,
    norm_defs,
    padded_vocab,
)
from repro.models.params import ParamDef, stack_defs


@dataclass(frozen=True)
class Stage:
    unit: tuple[str, ...]     # block kinds within one scanned unit
    count: int                # number of units scanned


def build_stages(cfg: ModelConfig) -> tuple[Stage, ...]:
    types = cfg.layer_types()
    if len(set(types)) == 1:
        return (Stage((types[0],), len(types)),)
    if cfg.block_pattern:
        p = len(cfg.block_pattern)
        n_full, rem = divmod(len(types), p)
        stages = [Stage(tuple(cfg.block_pattern), n_full)] if n_full else []
        if rem:
            stages.append(Stage(tuple(types[n_full * p:]), 1))
        return tuple(stages)
    # run-length group consecutive identical types (first_k_dense etc.)
    stages: list[Stage] = []
    i = 0
    while i < len(types):
        j = i
        while j < len(types) and types[j] == types[i]:
            j += 1
        stages.append(Stage((types[i],), j - i))
        i = j
    if len(stages) > 6:
        raise ValueError(
            f"{cfg.name}: layer pattern fragments into {len(stages)} stages; "
            "set block_pattern explicitly for cyclic layouts"
        )
    return tuple(stages)


# ---------------------------------------------------------------------------
# per-block param defs
# ---------------------------------------------------------------------------

def block_defs(kind: str, cfg: ModelConfig, geom: AttnGeometry) -> dict:
    if kind in ("attn", "local"):
        d = {"ln1": norm_defs(cfg), "attn": attn_mod.attn_defs(cfg, geom)}
        if cfg.parallel_block:
            d["mlp"] = mlp_defs(cfg)
        else:
            d["ln2"] = norm_defs(cfg)
            d["mlp"] = mlp_defs(cfg)
        return d
    if kind == "moe":
        return {
            "ln1": norm_defs(cfg),
            "attn": attn_mod.attn_defs(cfg, geom),
            "ln2": norm_defs(cfg),
            "moe": moe_mod.moe_defs(cfg),
        }
    if kind == "ssm":
        return {"ln1": norm_defs(cfg), "ssm": ssm_mod.ssm_defs(cfg)}
    if kind == "rec":
        return {
            "ln1": norm_defs(cfg),
            "rec": rec_mod.rec_defs(cfg),
            "ln2": norm_defs(cfg),
            "mlp": mlp_defs(cfg),
        }
    raise ValueError(f"unknown block kind {kind!r}")


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

class Model:
    """A config bound to a TP width (for head padding / kv replication).

    ``constrain`` is an optional ``fn(x, logical_axes) -> x`` injected by the
    distribution layer. The model sees the mesh only where a kernel must run
    per shard (``moe_mesh``, ``mesh``).
    """

    def __init__(self, cfg: ModelConfig, tp: int = 1,
                 constrain: Callable | None = None,
                 remat: str = "none", act_dtype=jnp.bfloat16,
                 moe_mesh=None, mesh=None):
        self.cfg = cfg
        self.geom = resolve_geometry(cfg, tp) if cfg.n_heads else None
        self.stages = build_stages(cfg)
        self.constrain = constrain or (lambda x, spec: x)
        self.remat = remat
        self.act_dtype = act_dtype
        # mesh for the shard_map EP dispatch (None -> pure-XLA fallback);
        # moe_batch_axes: None = derive from mesh, () = caller is already
        # manual over the batch axes (explicit-ABI path)
        self.moe_mesh = moe_mesh
        self.moe_batch_axes = None
        # the device mesh, for kernels XLA cannot partition (the paged
        # attention Pallas call runs per shard); None -> one device
        self.mesh = mesh

    # -- params ---------------------------------------------------------
    def param_defs(self) -> dict:
        cfg = self.cfg
        d: dict = {"embed": embed_defs(cfg), "final_norm": norm_defs(cfg)}
        for si, st in enumerate(self.stages):
            unit = {
                f"b{bi}": block_defs(kind, cfg, self.geom)
                for bi, kind in enumerate(st.unit)
            }
            d[f"stage{si}"] = stack_defs(unit, st.count)
        return d

    # -- caches -----------------------------------------------------------
    def cache_defs(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        cfg = self.cfg
        d: dict = {}
        for si, st in enumerate(self.stages):
            unit: dict = {}
            for bi, kind in enumerate(st.unit):
                entry = self._block_cache_defs(kind, batch, max_len, dtype)
                if entry:
                    unit[f"b{bi}"] = stack_defs(entry, st.count)
            d[f"stage{si}"] = unit
        return d

    def _block_cache_defs(self, kind: str, batch: int, max_len: int, dtype) -> dict:
        cfg = self.cfg
        if kind in ("attn", "local", "moe"):
            g = self.geom
            S = min(cfg.window, max_len) if (kind == "local" or
                                             (cfg.attn_kind == "local" and cfg.window)) else max_len
            spec = ("batch", "kv_seq", "kv_heads", None)
            return {
                "k": ParamDef((batch, S, g.n_kv, g.head_dim), spec, "zeros"),
                "v": ParamDef((batch, S, g.n_kv, g.head_dim), spec, "zeros"),
            }
        if kind == "ssm":
            di, ds = cfg.d_inner, cfg.ssm_state
            return {
                "conv": ParamDef((batch, cfg.conv_kernel - 1, di + 2 * ds),
                                 ("batch", None, "rnn"), "zeros"),
                "state": ParamDef((batch, cfg.ssm_heads, ds, cfg.ssm_headdim),
                                  ("batch", "heads", None, None), "zeros"),
            }
        if kind == "rec":
            R = cfg.rnn_width_
            return {
                "conv": ParamDef((batch, cfg.conv_kernel - 1, R),
                                 ("batch", None, "rnn"), "zeros"),
                "state": ParamDef((batch, R), ("batch", "rnn"), "zeros"),
            }
        return {}

    def paged_cache_defs(self, n_pages: int, page_size: int,
                         dtype=jnp.bfloat16) -> dict:
        """Cache defs for PAGED serving: per attention layer one global page
        pool ``(n_kv, n_pages, page_size, hd)`` shared by every slot through
        the host-side page table (one table for all layers -- allocation is
        identical layer-to-layer). Ring-buffer (windowed) and recurrent
        caches are per-slot state, not pageable history -> unsupported."""
        cfg = self.cfg
        if cfg.attn_kind == "local" and cfg.window:
            raise NotImplementedError(
                "paged KV cache does not support sliding-window attention "
                "(ring-buffer caches stay contiguous)")
        d: dict = {}
        for si, st in enumerate(self.stages):
            unit: dict = {}
            for bi, kind in enumerate(st.unit):
                if kind in ("ssm", "rec", "local"):
                    raise NotImplementedError(
                        f"paged KV cache does not support {kind!r} blocks "
                        "(windowed/recurrent caches stay contiguous)")
                g = self.geom
                spec = ("kv_heads", None, None, None)
                unit[f"b{bi}"] = stack_defs({
                    "k": ParamDef((g.n_kv, n_pages, page_size, g.head_dim),
                                  spec, "zeros"),
                    "v": ParamDef((g.n_kv, n_pages, page_size, g.head_dim),
                                  spec, "zeros"),
                }, st.count)
            d[f"stage{si}"] = unit
        return d

    # -- forward (train / prefill) ------------------------------------------
    def forward(self, params: dict, tokens: jax.Array,
                frontend_embeds: jax.Array | None = None,
                frontend_len: jax.Array | None = None,
                collect_cache: bool = False, cache_len: int | None = None,
                prefix_kv: dict | None = None,
                prefix_pages: jax.Array | None = None,
                prefix_len: int = 0):
        """tokens: (B, S_tok). Returns logits (B,S,Vp) [, cache].

        ``frontend_embeds`` (B, F, D) is a modality prefix prepended ahead of
        the token embeddings. ``frontend_len`` (scalar or (B,)) marks how many
        of the F buffer rows are real: the prefix and tokens are then packed
        contiguously (real frontend rows, then tokens, then all the right-pad
        garbage) so positions stay gap-free and the causal mask hides every
        pad row -- the serving path's right-pad contract. With
        ``frontend_len == F`` the pack is the identity gather, bitwise equal
        to the plain concatenation the train path uses.

        SUFFIX prefill over a shared KV prefix (the paged prefix cache):
        with ``prefix_len > 0`` (static), ``tokens`` are only the UNCACHED
        suffix of a prompt whose first ``prefix_len`` positions already sit
        in the paged pool ``prefix_kv`` (the per-stage paged_cache_defs
        tree) at the physical pages listed in ``prefix_pages``
        ((ceil(prefix_len / page_size),) int32 -- the last page may be only
        partially covered when the shared prefix ends mid-page; positions
        past ``prefix_len`` are sliced off). Token positions are offset past
        the prefix (RoPE included) and every attention block gathers the
        prefix pages and attends over [prefix, suffix]; the collected cache
        covers the SUFFIX positions only. Full-attention archs only --
        exactly the archs the paged pool itself admits."""
        cfg = self.cfg
        dtype = self.act_dtype
        if prefix_len:
            if frontend_embeds is not None:
                raise NotImplementedError(
                    "prefix-cached suffix prefill does not compose with "
                    "frontend embeddings")
            if prefix_kv is None or prefix_pages is None:
                raise ValueError("prefix_len > 0 needs prefix_kv + "
                                 "prefix_pages")
        x = embed_tokens(params["embed"], tokens, cfg, dtype)
        if frontend_embeds is not None:
            fe = frontend_embeds.astype(dtype)
            x = jnp.concatenate([fe, x], axis=1)
            if frontend_len is not None:
                F, S = fe.shape[1], x.shape[1]
                fl = jnp.broadcast_to(
                    jnp.asarray(frontend_len, jnp.int32),
                    (x.shape[0],))[:, None]
                pos = jnp.arange(S, dtype=jnp.int32)[None, :]
                src = jnp.where(pos < fl, pos, pos + (F - fl))
                src = jnp.minimum(src, S - 1)   # tail rows: clamped garbage
                x = jnp.take_along_axis(x, src[:, :, None], axis=1)
        B, S, _ = x.shape
        x = self.constrain(x, ("batch", "seq", "embed"))
        # (1, S): positions are batch-independent in train/prefill, so the
        # causal mask materialises as (1, Sq, Sk) instead of (B, Sq, Sk).
        # Suffix prefill offsets them past the cached prefix.
        positions = prefix_len + jnp.arange(S, dtype=jnp.int32)[None, :]

        caches = {}
        aux_total = jnp.zeros((), jnp.float32)
        for si, st in enumerate(self.stages):
            body = self._make_body(st, positions, collect_cache,
                                   cache_len or S,
                                   prefix_pages=prefix_pages,
                                   prefix_len=prefix_len)
            if self.remat != "none":
                body = _remat(body, self.remat)
            xs = ((params[f"stage{si}"], prefix_kv[f"stage{si}"])
                  if prefix_len else params[f"stage{si}"])
            (x, aux), ys = jax.lax.scan(body, (x, aux_total), xs)
            aux_total = aux
            if collect_cache:
                caches[f"stage{si}"] = ys
        x = apply_norm(params["final_norm"], x, cfg.norm)
        logits = lm_logits(params["embed"], x, cfg)
        logits = self.constrain(logits, ("batch", "seq", "vocab"))
        if collect_cache:
            return logits, caches, aux_total
        return logits, aux_total

    def _make_body(self, st: Stage, positions, collect_cache: bool,
                   cache_len: int, prefix_pages=None, prefix_len: int = 0):
        cfg, geom = self.cfg, self.geom

        def body(carry, xs):
            x, aux = carry
            unit_params, unit_prefix = xs if prefix_len else (xs, None)
            entries = {}
            for bi, kind in enumerate(st.unit):
                p = unit_params[f"b{bi}"]
                pkv = unit_prefix.get(f"b{bi}") if unit_prefix else None
                x, aux_b, entry = self._apply_block(kind, p, x, positions,
                                                    collect_cache, cache_len,
                                                    prefix_kv=pkv,
                                                    prefix_pages=prefix_pages,
                                                    prefix_len=prefix_len)
                aux = aux + aux_b
                if collect_cache and entry is not None:
                    entries[f"b{bi}"] = entry
            return (x, aux), (entries if collect_cache else None)

        return body

    def _apply_block(self, kind: str, p: dict, x, positions,
                     collect_cache: bool, cache_len: int,
                     prefix_kv=None, prefix_pages=None, prefix_len: int = 0):
        cfg, geom = self.cfg, self.geom
        aux = jnp.zeros((), jnp.float32)
        entry = None
        window = cfg.window if (kind == "local" or cfg.attn_kind == "local") else 0
        if prefix_len and (window or kind in ("ssm", "rec")):
            raise NotImplementedError(
                "prefix-cached suffix prefill supports full attention only")

        if kind in ("attn", "local", "moe"):
            h = apply_norm(p["ln1"], x, cfg.norm)
            q, k, v = attn_mod.project_qkv(p["attn"], h, cfg, geom, positions)
            q = self.constrain(q, ("batch", "seq", "heads", None))
            k = self.constrain(k, ("batch", "kv_seq", "kv_heads", None))
            v = self.constrain(v, ("batch", "kv_seq", "kv_heads", None))
            k_all, v_all, kv_pos = k, v, positions
            if prefix_len:
                # gather the cached prefix pages (n_kv, kp, ps, hd) into a
                # contiguous (B, prefix_len, n_kv, hd) history ahead of the
                # suffix KV; kv positions run 0..prefix_len+S-1 while the q
                # positions stay offset past the prefix. prefix_len may end
                # MID-page (radix partial match): the last page is gathered
                # whole and the tail positions past prefix_len sliced off
                B, S = k.shape[0], k.shape[1]
                def _gather(pool):
                    n_kv, _, ps_, hd = pool.shape
                    pg = jnp.take(pool, prefix_pages, axis=1)
                    pg = pg.reshape(n_kv, -1, hd)[:, :prefix_len]
                    pg = pg.transpose(1, 0, 2)
                    return jnp.broadcast_to(pg[None], (B, prefix_len, n_kv, hd))
                k_all = jnp.concatenate(
                    [_gather(prefix_kv["k"]).astype(k.dtype), k], axis=1)
                v_all = jnp.concatenate(
                    [_gather(prefix_kv["v"]).astype(v.dtype), v], axis=1)
                kv_pos = jnp.arange(prefix_len + S, dtype=jnp.int32)[None, :]
            ctx = attn_mod.attend(q, k_all, v_all, positions, kv_pos, window,
                                  score_dtype=jnp.dtype(cfg.attn_score_dtype),
                                  q_chunk=cfg.attn_q_chunk,
                                  kv_chunk=cfg.attn_kv_chunk,
                                  q_offset=prefix_len)
            attn_out = attn_mod.attn_out(p["attn"], ctx)
            if collect_cache:
                entry = self._prefill_cache_entry(k, v, window, cache_len)
            if cfg.parallel_block:
                x = x + attn_out + apply_mlp(p["mlp"], h, cfg.mlp)
            else:
                x = x + attn_out
                h2 = apply_norm(p["ln2"], x, cfg.norm)
                if kind == "moe":
                    moe_out, aux = self._moe(p["moe"], h2)
                    x = x + moe_out
                else:
                    x = x + apply_mlp(p["mlp"], h2, cfg.mlp)
        elif kind == "ssm":
            h = apply_norm(p["ln1"], x, cfg.norm)
            if collect_cache:
                out, entry = _ssm_prefill(p["ssm"], h, cfg)
            else:
                out = ssm_mod.ssm_forward(p["ssm"], h, cfg)
            x = x + out
        elif kind == "rec":
            h = apply_norm(p["ln1"], x, cfg.norm)
            if collect_cache:
                out, entry = _rec_prefill(p["rec"], h, cfg)
            else:
                out = rec_mod.rec_forward(p["rec"], h, cfg)
            x = x + out
            h2 = apply_norm(p["ln2"], x, cfg.norm)
            x = x + apply_mlp(p["mlp"], h2, cfg.mlp)
        else:
            raise ValueError(kind)
        x = self.constrain(x, ("batch", "seq", "embed"))
        return x, aux, entry

    def _moe(self, p_moe, h):
        if self.moe_mesh is not None:
            return moe_mod.moe_forward_spmd(p_moe, h, self.cfg, self.moe_mesh,
                                            batch_axes=self.moe_batch_axes)
        return moe_mod.moe_forward(p_moe, h, self.cfg, self.constrain)

    def _prefill_cache_entry(self, k, v, window: int, cache_len: int):
        """Store the last ``cache_len`` (or window) positions into the cache.

        Windowed caches are *ring buffers* with the invariant that position p
        lives at slot ``p % ring``; the kept tail must be rolled into that
        layout or the first decoded tokens attend to permuted history."""
        S = k.shape[1]
        keep = min(window, cache_len) if window else cache_len
        if S >= keep:
            k_, v_ = k[:, S - keep:], v[:, S - keep:]
            if window:
                k_ = jnp.roll(k_, S % keep, axis=1)
                v_ = jnp.roll(v_, S % keep, axis=1)
        else:
            pad = keep - S
            k_ = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_ = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return {"k": k_, "v": v_}

    # -- decode ------------------------------------------------------------
    def decode_step(self, params: dict, cache: dict, tokens: jax.Array,
                    idx: jax.Array, page_table: jax.Array | None = None):
        """tokens: (B,1); idx: int32 position -- scalar (lockstep batch) or
        (B,) per-row positions (slot-granular continuous batching).
        -> (logits, new_cache).

        With ``page_table`` (B, max_pages) the attention caches are PAGED
        pools (see paged_cache_defs) and decode routes through the paged
        kernel; without it the caches are contiguous per-slot slabs.

        The cache rides in the scan CARRY and is updated in place with
        dynamic_update_index (params are dynamically indexed per layer).
        The earlier xs->ys formulation made XLA hold 3-4 functional copies
        of the multi-GB cache in while-loop temps (observed: 47 GiB temp
        against an 11.9 GiB cache on deepseek decode_32k); carry aliasing
        plus donated inputs keeps it at ~1 copy (EXPERIMENTS.md §Perf)."""
        cfg = self.cfg
        dtype = self.act_dtype
        x = embed_tokens(params["embed"], tokens, cfg, dtype)
        x = self.constrain(x, ("batch", "seq", "embed"))
        new_cache: dict = {}
        for si, st in enumerate(self.stages):
            body = self._make_decode_body(st, idx, page_table)
            stage_params = params[f"stage{si}"]

            def carry_body(carry, i, body=body, stage_params=stage_params):
                x, scache = carry
                up = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                    stage_params)
                uc = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                    scache)
                (x,), entries = body((x,), (up, uc))
                scache = jax.tree.map(
                    lambda buf, new: jax.lax.dynamic_update_index_in_dim(
                        buf, new.astype(buf.dtype), i, 0),
                    scache, entries)
                return (x, scache), None

            (x, sc), _ = jax.lax.scan(
                carry_body, (x, cache[f"stage{si}"]),
                jnp.arange(st.count))
            new_cache[f"stage{si}"] = sc
        x = apply_norm(params["final_norm"], x, cfg.norm)
        logits = lm_logits(params["embed"], x, cfg)
        return logits, new_cache

    def _make_decode_body(self, st: Stage, idx, page_table=None):
        cfg, geom = self.cfg, self.geom

        def body(carry, xs):
            (x,) = carry
            unit_params, unit_cache = xs
            new_entries = {}
            for bi, kind in enumerate(st.unit):
                p = unit_params[f"b{bi}"]
                c = unit_cache.get(f"b{bi}") if unit_cache else None
                window = cfg.window if (kind == "local" or
                                        cfg.attn_kind == "local") else 0
                if kind in ("attn", "local", "moe"):
                    h = apply_norm(p["ln1"], x, cfg.norm)
                    if page_table is not None:
                        out, nc = attn_mod.paged_decode_attn(
                            p["attn"], h, c, idx, page_table, cfg, geom,
                            window, mesh=self.mesh)
                    else:
                        out, nc = attn_mod.decode_attn(p["attn"], h, c, idx,
                                                       cfg, geom, window)
                    if cfg.parallel_block:
                        x = x + out + apply_mlp(p["mlp"], h, cfg.mlp)
                    else:
                        x = x + out
                        h2 = apply_norm(p["ln2"], x, cfg.norm)
                        if kind == "moe":
                            mo, _ = self._moe(p["moe"], h2)
                            x = x + mo
                        else:
                            x = x + apply_mlp(p["mlp"], h2, cfg.mlp)
                elif kind == "ssm":
                    h = apply_norm(p["ln1"], x, cfg.norm)
                    out, nc = ssm_mod.ssm_decode(p["ssm"], h, c, cfg)
                    x = x + out
                elif kind == "rec":
                    h = apply_norm(p["ln1"], x, cfg.norm)
                    out, nc = rec_mod.rec_decode(p["rec"], h, c, cfg)
                    x = x + out
                    h2 = apply_norm(p["ln2"], x, cfg.norm)
                    x = x + apply_mlp(p["mlp"], h2, cfg.mlp)
                else:
                    raise ValueError(kind)
                new_entries[f"b{bi}"] = nc
            return (x,), new_entries

        return body


    # -- per-unit cost probes (dry-run roofline scan correction) --------------
    def unit_param_defs(self, si: int) -> dict:
        return {
            f"b{bi}": block_defs(kind, self.cfg, self.geom)
            for bi, kind in enumerate(self.stages[si].unit)
        }

    def unit_cache_defs(self, si: int, batch: int, max_len: int, dtype) -> dict:
        out = {}
        for bi, kind in enumerate(self.stages[si].unit):
            entry = self._block_cache_defs(kind, batch, max_len, dtype)
            if entry:
                out[f"b{bi}"] = entry
        return out

    def unit_probe(self, si: int, kind: str):
        """A standalone function whose HLO cost == one scan iteration of
        stage ``si`` (XLA's cost analysis counts while bodies once; the
        dry-run multiplies these probes by (count-1) to correct totals).

        kind: 'train' (fwd+bwd), 'prefill' (fwd + cache collect),
              'decode' (one-token step with cache update)."""
        st = self.stages[si]

        def fwd(unit_params, x, positions, collect):
            body = self._make_body(st, positions, collect, x.shape[1])
            if self.remat != "none" and kind == "train":
                body = _remat(body, self.remat)
            (x2, aux), ys = body((x, jnp.zeros((), jnp.float32)), unit_params)
            return x2, aux, ys

        if kind == "train":
            def probe(unit_params, x, positions):
                def loss(up, xx):
                    x2, aux, _ = fwd(up, xx, positions, False)
                    return jnp.sum(x2.astype(jnp.float32) ** 2) * 1e-6 + aux
                gp, gx = jax.grad(loss, argnums=(0, 1))(unit_params, x)
                return gp, gx
            return probe
        if kind == "prefill":
            def probe(unit_params, x, positions):
                x2, aux, ys = fwd(unit_params, x, positions, True)
                return x2, aux, ys
            return probe
        if kind == "decode":
            def probe(unit_params, unit_cache, x, idx):
                body = self._make_decode_body(st, idx)
                (x2,), entries = body((x,), (unit_params, unit_cache))
                return x2, entries
            return probe
        raise ValueError(kind)


# ---------------------------------------------------------------------------
# prefill variants that also return final recurrent states
# ---------------------------------------------------------------------------

def _ssm_prefill(p, x, cfg: ModelConfig):
    """ssm_forward with the decode cache (final chunk state + conv tail)."""
    return ssm_mod.ssm_forward(p, x, cfg, return_cache=True)


def _rec_prefill(p, x, cfg: ModelConfig):
    dt_ = x.dtype
    y = jax.nn.gelu(x @ p["wy"].astype(dt_))
    raw = x @ p["wx"].astype(dt_)
    xr = rec_mod._causal_conv(raw, p["conv_w"].astype(dt_), p["conv_b"].astype(dt_))
    a, b = rec_mod._rglru_coeffs(p, xr)
    h = rec_mod.rglru_scan(a, b)
    out = (h.astype(dt_) * y) @ p["wo"].astype(dt_)
    return out, {"conv": raw[:, -(cfg.conv_kernel - 1):], "state": h[:, -1]}


def _remat(body, mode: str):
    if mode == "full":
        return jax.checkpoint(body, policy=None)
    if mode == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    raise ValueError(f"unknown remat mode {mode!r}")
