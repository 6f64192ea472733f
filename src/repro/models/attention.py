"""GQA attention: train/prefill (full + local window) and cached decode.

TP geometry
-----------
The assigned configs have head counts that do not always divide the 16-way
model axis (llama3.2: 24 q heads; musicgen: 24; llama4: 40; recurrentgemma:
10 MQA). We therefore resolve an ``AttnGeometry`` at runtime-bind time:

  * q heads physically padded to a multiple of TP (Megatron's
    ``make_vocab_size_divisible_by`` applied to heads); padded heads get
    zero-init wq/wo rows so they are exact no-ops numerically;
  * kv heads replicated by the smallest integer r such that kv*r divides the
    padded q heads AND is divisible by TP -- this is the standard
    "KV replication for TP > n_kv_heads" trick (MaxText); it's what lets the
    32k/500k KV *cache* shard over the model axis instead of replicating
    ~100GB per chip.

The padding overhead is honest, visible compute: it is counted in HLO_FLOPs
and reported in the roofline MODEL_FLOPS/HLO_FLOPs ratio.

Long sequences
--------------
Full-softmax scores for prefill_32k would be (B,H,32k,32k) -- hundreds of GB.
``attend`` therefore switches to a chunked online-softmax (flash-style
lax.scan over KV chunks, running max/denominator) above a size threshold.
On TPU the Pallas kernel (repro.kernels.flash_attention) replaces this path;
the XLA formulation here is its oracle and the dry-run/compile path.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.mesh import batch_axes
from repro.models.config import ModelConfig
from repro.models.layers import apply_rope
from repro.models.params import ParamDef

NEG_INF = -1e30
CHUNKED_KV_THRESHOLD = 8192   # use online-softmax scan above this many keys
KV_CHUNK = 2048
Q_CHUNK = 2048


@dataclass(frozen=True)
class AttnGeometry:
    n_q: int          # padded q heads
    n_q_orig: int
    n_kv: int         # replicated (and, for MHA, padded) kv heads
    n_kv_orig: int
    head_dim: int

    @property
    def q_per_kv(self) -> int:
        return self.n_q // self.n_kv

    @property
    def kv_rep(self) -> int:
        return self.n_kv // self.n_kv_orig if self.n_kv % self.n_kv_orig == 0 else 0


def resolve_geometry(cfg: ModelConfig, tp: int) -> AttnGeometry:
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    hp = -(-h // tp) * tp if h % tp else h            # pad q heads to TP multiple
    if kv == h:                                        # MHA: kv pads with q
        kvp = hp
    else:
        r = 1
        while r <= tp and ((kv * r) % tp or hp % (kv * r)):
            r += 1
        kvp = kv * r if r <= tp else hp                # fallback: full replication
    return AttnGeometry(hp, h, kvp, kv, hd)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attn_defs(cfg: ModelConfig, geom: AttnGeometry) -> dict:
    D, hd = cfg.d_model, geom.head_dim
    return {
        # padded q/o slots exist physically; zero-padding is applied by the
        # init mask below (fan_in init then multiplied by the validity mask
        # at apply time would cost flops -- instead padded slots simply learn;
        # they are dead weight only w.r.t. the canonical checkpoint format).
        "wq": ParamDef((D, geom.n_q, hd), ("embed", "heads", None)),
        "wk": ParamDef((D, geom.n_kv_orig, hd), ("embed", None, None)),
        "wv": ParamDef((D, geom.n_kv_orig, hd), ("embed", None, None)),
        "wo": ParamDef((geom.n_q, hd, D), ("heads", None, "embed")),
    }


# ---------------------------------------------------------------------------
# score-path helpers
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, window: int) -> jax.Array:
    """(…, Sq, Sk) additive mask: causal, optionally sliding-window."""
    ok = k_pos[..., None, :] <= q_pos[..., :, None]
    ok &= k_pos[..., None, :] >= 0           # ring-buffer slots not yet written
    if window:
        ok &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa_dense(q, k, v, q_pos, k_pos, window, scale,
                score_dtype=jnp.float32) -> jax.Array:
    """q: (B,Sq,Hkv,G,hd)  k,v: (B,Sk,Hkv,hd)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32) * scale
    s = s + _mask_bias(q_pos, k_pos, window)[:, None, None]
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v)


def _sdpa_chunked(q, k, v, q_pos, k_pos, window, scale,
                  score_dtype=jnp.float32,
                  q_chunk=None, kv_chunk=None, q_offset: int = 0) -> jax.Array:
    """Online-softmax over KV chunks (flash-style, XLA formulation).

    Memory: O(Sq * KV_CHUNK) scores instead of O(Sq * Sk).

    The chunk loop is STATICALLY UNROLLED (python for), not lax.scan:
    XLA's HloCostAnalysis counts a while-loop body once regardless of trip
    count, which would under-report attention FLOPs/bytes by nchunks in the
    dry-run roofline. Unrolled chunks are counted exactly, and XLA's
    scheduler can overlap chunk DMA with compute (what the Pallas kernel
    does explicitly on TPU). Fully-causal (all-masked) chunk/q-block pairs
    are skipped at trace time -- the same block-sparsity the Pallas kernel
    exploits -- so causal attention costs ~half of the rectangular count.
    """
    B, Sq, Hkv, G, hd = q.shape
    Sk = k.shape[1]
    KV_CHUNK = kv_chunk or globals()["KV_CHUNK"]
    Q_CHUNK = q_chunk or globals()["Q_CHUNK"]
    nchunks = -(-Sk // KV_CHUNK)
    pad = nchunks * KV_CHUNK - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)),
                        constant_values=jnp.iinfo(jnp.int32).max)

    # q is chunked too so trace-time causal skipping applies per (qi, ki)
    nq = -(-Sq // Q_CHUNK)
    qpad = nq * Q_CHUNK - Sq
    if qpad:
        q = jnp.pad(q, ((0, 0), (0, qpad), (0, 0), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, qpad)), constant_values=-1)

    # static per-chunk position bounds: q_pos/k_pos are data, but for the
    # skip decision we rely on the canonical layout (positions ascending
    # from ``q_offset`` -- 0 for train/prefill, the shared-prefix length
    # for prefix-cached suffix prefill, whose queries see prefix keys at
    # positions BELOW their own block index); decode (Sq==1) never skips.
    causal_layout = Sq > 1
    out_qchunks = []
    for qi in range(nq):
        qb = jax.lax.slice_in_dim(q, qi * Q_CHUNK, (qi + 1) * Q_CHUNK, axis=1)
        qpb = jax.lax.slice_in_dim(q_pos, qi * Q_CHUNK, (qi + 1) * Q_CHUNK, axis=1)
        m = jnp.full((B, Hkv, G, Q_CHUNK), NEG_INF, jnp.float32)
        l = jnp.zeros((B, Hkv, G, Q_CHUNK), jnp.float32)
        acc = jnp.zeros((B, Hkv, G, Q_CHUNK, hd), jnp.float32)
        q_lo = q_offset + qi * Q_CHUNK            # min q position in block
        q_hi = q_offset + (qi + 1) * Q_CHUNK - 1
        for ki in range(nchunks):
            k_lo = ki * KV_CHUNK
            if causal_layout:
                if k_lo > q_hi:                   # fully future: skip
                    continue
                if window and (ki + 1) * KV_CHUNK - 1 < q_lo - window + 1:
                    continue                      # fully out of window: skip
            kb = jax.lax.slice_in_dim(k, k_lo, k_lo + KV_CHUNK, axis=1)
            vb = jax.lax.slice_in_dim(v, k_lo, k_lo + KV_CHUNK, axis=1)
            pb = jax.lax.slice_in_dim(k_pos, k_lo, k_lo + KV_CHUNK, axis=1)
            if score_dtype == jnp.float32:
                s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,
                               preferred_element_type=jnp.float32) * scale
                s = s + _mask_bias(qpb, pb, window)[:, None, None]
                m_new = jnp.maximum(m, s.max(axis=-1))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new[..., None])
                l = l * alpha + p.sum(axis=-1)
                pv = p.astype(vb.dtype)
            else:
                # low-precision score chain: the (bq x bk) arrays -- the
                # dominant HBM traffic of XLA attention -- stay in bf16;
                # running max/denominator/accumulator stay f32.
                s = (jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,
                                preferred_element_type=score_dtype)
                     * jnp.asarray(scale, score_dtype))
                s = s + _mask_bias(qpb, pb, window)[:, None, None].astype(
                    score_dtype)
                m_new = jnp.maximum(m, s.max(axis=-1).astype(jnp.float32))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new[..., None].astype(score_dtype))
                l = l * alpha + jnp.sum(p, axis=-1, dtype=jnp.float32)
                pv = p.astype(vb.dtype)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", pv, vb,
                preferred_element_type=jnp.float32)
            m = m_new
        out_qchunks.append(acc / jnp.maximum(l, 1e-30)[..., None])
    out = jnp.concatenate(out_qchunks, axis=3)     # (B,Hkv,G,Sq+pad,hd)
    out = out.transpose(0, 3, 1, 2, 4).astype(q.dtype)
    return out[:, :Sq] if qpad else out


def attend(q, k, v, q_pos, k_pos, window: int = 0,
           score_dtype=jnp.float32, q_chunk=None, kv_chunk=None,
           q_offset: int = 0) -> jax.Array:
    """Grouped attention. q: (B,Sq,Hq,hd) -> (B,Sq,Hq,hd).

    k/v carry the *replicated* kv heads (geom.n_kv). ``q_offset`` is the
    STATIC base of the canonical q positions (nonzero only for the
    prefix-cached suffix prefill) -- the chunked path's trace-time causal
    skipping must know it, or it would skip KV chunks that sit between the
    0-based block index and the true offset positions."""
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Sq, Hkv, Hq // Hkv, hd)
    if k.shape[1] > CHUNKED_KV_THRESHOLD or score_dtype != jnp.float32:
        out = _sdpa_chunked(qg, k, v, q_pos, k_pos, window, scale,
                            score_dtype, q_chunk=q_chunk, kv_chunk=kv_chunk,
                            q_offset=q_offset)
    else:
        out = _sdpa_dense(qg, k, v, q_pos, k_pos, window, scale)
    return out.reshape(B, Sq, Hq, hd)


# ---------------------------------------------------------------------------
# block forward paths
# ---------------------------------------------------------------------------

def project_qkv(p: dict, x: jax.Array, cfg: ModelConfig, geom: AttnGeometry,
                positions: jax.Array):
    """x: (B,S,D) -> q (B,S,Hq,hd), k/v (B,S,n_kv,hd) with RoPE + replication."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_pct)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_pct)
    k, v = replicate_kv(k, geom), replicate_kv(v, geom)
    return q, k, v


def replicate_kv(kv: jax.Array, geom: AttnGeometry) -> jax.Array:
    """(…, n_kv_orig, hd) -> (…, n_kv, hd).

    Gather-based replication: target slot j serves padded q heads
    [j*g, (j+1)*g) and reads the kv head the FIRST of those q heads uses in
    the canonical (unpadded) grouping. For divisible cases this equals
    jnp.repeat; for padded MHA the extra slots alias the last canonical
    head (the padded q heads are additional learned heads either way)."""
    h = kv.shape[-2]
    if h == geom.n_kv:
        return kv
    g = geom.q_per_kv
    group = max(1, geom.n_q_orig // h)          # canonical q-heads per kv
    q0 = jnp.minimum(jnp.arange(geom.n_kv) * g, geom.n_q_orig - 1)
    idx = jnp.minimum(q0 // group, h - 1)
    return jnp.take(kv, idx, axis=-2)


def attn_out(p: dict, ctx: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", ctx, p["wo"].astype(ctx.dtype))


def attn_forward(p: dict, x: jax.Array, cfg: ModelConfig, geom: AttnGeometry,
                 positions: jax.Array, window: int = 0) -> jax.Array:
    q, k, v = project_qkv(p, x, cfg, geom, positions)
    ctx = attend(q, k, v, positions, positions, window)
    return attn_out(p, ctx)


# ---------------------------------------------------------------------------
# decode with KV cache
# ---------------------------------------------------------------------------

def init_kv_cache(n_layers: int, batch: int, max_len: int, geom: AttnGeometry,
                  dtype) -> dict:
    shp = (n_layers, batch, max_len, geom.n_kv, geom.head_dim)
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}


def kv_cache_specs(window: int = 0):
    """Logical axes of one layer-stack's cache entry."""
    spec = ("layers", "batch", "kv_seq", "kv_heads", None)
    return {"k": spec, "v": spec}


def paged_decode_attn(p: dict, x: jax.Array, layer_cache: dict,
                      idx: jax.Array, page_table: jax.Array,
                      cfg: ModelConfig, geom: AttnGeometry,
                      window: int = 0, mesh=None):
    """One-token decode over a PAGED KV pool (slot-granular only).

    x: (B,1,D); layer_cache k/v: (n_kv, n_pages, page_size, hd) -- the
    layer's global page pool; idx: (B,) per-row positions; page_table:
    (B, max_pages) physical page ids (garbage page 0 where unmapped).

    Writes the new token's K/V into page ``table[b, idx//ps]`` at offset
    ``idx % ps`` (the scheduler guarantees that page is allocated before
    dispatch -- alloc-on-write happens host-side in the PagePool), then
    attends through repro.kernels.paged_attention (Pallas on TPU, XLA
    oracle elsewhere). Free slots write through table rows reset to the
    garbage page; their output is discarded by the host.

    On a ``mesh`` of several devices the attention call runs under
    ``shard_map``: XLA cannot partition a Pallas call, so each device runs
    it on its own batch rows against the whole pool."""
    if window:
        raise NotImplementedError(
            "paged decode supports full attention only (ring-buffer windows "
            "keep the contiguous per-slot layout)")
    from repro.kernels.paged_attention.ops import paged_attention
    B = x.shape[0]
    positions = idx[:, None].astype(jnp.int32)
    q, k, v = project_qkv(p, x, cfg, geom, positions)   # k/v: (B,1,n_kv,hd)
    n_kv, n_pages, ps, hd = layer_cache["k"].shape
    mp = page_table.shape[1]
    page = jnp.take_along_axis(
        page_table, jnp.clip(idx // ps, 0, mp - 1)[:, None], axis=1)[:, 0]
    off = idx % ps
    knew = jnp.moveaxis(k[:, 0], 1, 0)                  # (n_kv, B, hd)
    vnew = jnp.moveaxis(v[:, 0], 1, 0)
    ck = layer_cache["k"].at[:, page, off].set(
        knew.astype(layer_cache["k"].dtype))
    cv = layer_cache["v"].at[:, page, off].set(
        vnew.astype(layer_cache["v"].dtype))
    lengths = idx.astype(jnp.int32) + 1
    if mesh is None or mesh.size == 1:
        ctx = paged_attention(q[:, 0], ck, cv, page_table, lengths)
    else:
        shards = math.prod(mesh.shape[a] for a in batch_axes(mesh))
        rows = P(batch_axes(mesh)) if B % shards == 0 else P()
        ctx = jax.shard_map(
            paged_attention, mesh=mesh,
            in_specs=(rows, P(), P(), rows, rows), out_specs=rows,
            check_vma=False)(q[:, 0], ck, cv, page_table, lengths)
    return attn_out(p, ctx[:, None]), {"k": ck, "v": cv}


def decode_attn(p: dict, x: jax.Array, layer_cache: dict, idx: jax.Array,
                cfg: ModelConfig, geom: AttnGeometry, window: int = 0):
    """One-token decode. x: (B,1,D); layer_cache k/v: (B,S,n_kv,hd);
    idx: current position -- a scalar (whole-batch lockstep decode) or a
    (B,) vector of per-row positions (slot-granular continuous batching:
    every batch row is an independent request at its own depth).
    Returns (out, new_cache).

    For ``window`` caches the buffer is a ring of size window (positions are
    reconstructed modulo the ring)."""
    B = x.shape[0]
    per_slot = jnp.ndim(idx) == 1
    positions = (idx[:, None].astype(jnp.int32) if per_slot
                 else jnp.full((B, 1), idx, jnp.int32))
    q, k, v = project_qkv(p, x, cfg, geom, positions)
    S = layer_cache["k"].shape[1]
    slot = jnp.mod(idx, S) if window else idx
    if per_slot:
        # per-row writes: vmap the row update so each request lands at its
        # own position (XLA lowers this to one scatter, not B updates)
        upd = jax.vmap(
            lambda buf, new, s: jax.lax.dynamic_update_slice(
                buf, new, (s, 0, 0)))
        ck = upd(layer_cache["k"], k, slot)
        cv = upd(layer_cache["v"], v, slot)
    else:
        ck = jax.lax.dynamic_update_slice(layer_cache["k"], k, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(layer_cache["v"], v, (0, slot, 0, 0))
    if window:
        # ring buffer: true position of ring slot j given current write pos
        ring_idx = jnp.arange(S)
        if per_slot:
            k_pos = idx[:, None] - jnp.mod(slot[:, None] - ring_idx[None, :], S)
        else:
            k_pos = idx - jnp.mod(slot - ring_idx, S)
            k_pos = jnp.broadcast_to(k_pos, (B, S))
    else:
        k_pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    ctx = attend(q, ck, cv, positions, k_pos, window,
                 score_dtype=jnp.dtype(cfg.attn_score_dtype))
    return attn_out(p, ctx), {"k": ck, "v": cv}
