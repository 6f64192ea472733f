"""Plain float32 reference of a dense decoder-only transformer, as the
published Hugging Face implementations of Llama-style (RMSNorm, DeepSeek
LLM) and StableLM (LayerNorm with bias, partial rotary) models compute it:

    x = embed[tokens]
    per layer:  h = norm1(x);  q, k, v = h Wq, h Wk, h Wv
                q, k = rope(q), rope(k)   # rotate_half on the first
                                          # partial_rotary_factor * head_dim
                x += softmax(q k^T / sqrt(hd) + causal) v Wo   # GQA groups
                h = norm2(x);  x += (silu(h Wg) * (h Wu)) Wd
    logits = norm(x) Whead

It imports nothing of the program under test and reads only the
configuration's published keys. Weights come from ``weights_*`` below,
drawn from the seed layer by layer, so the reference regenerates each
layer when it needs it and never holds the whole model in float32.

``quant="fp8"`` is the control: every matrix product takes its operands
rounded to float8 e4m3 (per output column for weights, per row for
activations, each scaled to the format's range), the precision below the
bfloat16 the configurations serve in.
"""

from __future__ import annotations

import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256           # query rows per attention block
F8_MAX = 448.0          # largest float8_e4m3fn


def dims(cfg: dict) -> dict:
    """Sizes from the configuration's published keys."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    layernorm = "layer_norm_eps" in cfg
    rot = int(hd * cfg.get("partial_rotary_factor", 1.0))
    return dict(d=d, h=h, kv=cfg["num_key_value_heads"], hd=hd,
                f=cfg["intermediate_size"], layers=cfg["num_hidden_layers"],
                vocab=cfg["vocab_size"], layernorm=layernorm,
                eps=cfg["layer_norm_eps"] if layernorm else cfg["rms_norm_eps"],
                theta=float(cfg["rope_theta"]), rot=rot - rot % 2)


# ---------------------------------------------------------------------------
# weights: a fixed law per tensor, keyed by seed, tensor name and layer
# ---------------------------------------------------------------------------

def base_key(seed: int):
    """The run's key. Made outside any jit: seeds reach past 32 bits."""
    return jax.random.key(seed)


def _key(base, name: str, layer=0):
    k = jax.random.fold_in(base, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return jax.random.fold_in(k, layer)


def _uniform(key, shape, std: float, shift: float = 0.0):
    """Uniform with the given std from 16 random bits, exact in float32
    and rounded once to bfloat16, so every backend draws the same
    values."""
    b = jax.random.bits(key, shape, jnp.uint16).astype(jnp.int32)
    x = (2 * b - 65535).astype(jnp.float32) * jnp.float32(
        std * math.sqrt(3.0) / 65535)
    return (x + jnp.float32(shift)).astype(jnp.bfloat16)


def _norm_weights(base, name, layer, d, dm):
    out = {"scale": _uniform(_key(base, name + ".scale", layer), (dm,),
                             0.1, 1.0)}
    if d["layernorm"]:
        out["bias"] = _uniform(_key(base, name + ".bias", layer), (dm,), 0.1)
    return out


def weights_layer(base, layer, d: dict) -> dict:
    """One layer's tensors in bfloat16, in the published layout
    (head dims as Hugging Face's rotate_half convention orders them)."""
    D, H, KV, hd, F = d["d"], d["h"], d["kv"], d["hd"], d["f"]
    u = lambda name, shape, std: _uniform(_key(base, name, layer), shape, std)
    return {
        "ln1": _norm_weights(base, "ln1", layer, d, D),
        "wq": u("wq", (D, H, hd), 1 / math.sqrt(D)),
        "wk": u("wk", (D, KV, hd), 1 / math.sqrt(D)),
        "wv": u("wv", (D, KV, hd), 1 / math.sqrt(D)),
        "wo": u("wo", (H, hd, D), 1 / math.sqrt(H * hd)),
        "ln2": _norm_weights(base, "ln2", layer, d, D),
        "wg": u("wg", (D, F), 1 / math.sqrt(D)),
        "wu": u("wu", (D, F), 1 / math.sqrt(D)),
        "wd": u("wd", (F, D), 1 / math.sqrt(F)),
    }


def weights_embed(base, d: dict):
    return _uniform(_key(base, "embed"), (d["vocab"], d["d"]), 1.0)


def weights_head(base, d: dict) -> dict:
    return {"final": _norm_weights(base, "final", 0, d, d["d"]),
            "head": _uniform(_key(base, "head"), (d["d"], d["vocab"]),
                             1 / math.sqrt(d["d"]))}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _f8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, quant: str):
    """a: (..., K) @ w: (K, N) in float32, operands rounded for fp8."""
    if quant == "fp8":
        a, w = _f8(a, -1), _f8(w, 0)
    return a @ w


def _norm(x, p, d):
    if d["layernorm"]:
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + d["eps"]) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + d["eps"]) * p["scale"]


def _rope(x, pos, d):
    """rotate_half rotary embedding on the first ``rot`` dims of each head."""
    rot = d["rot"]
    if rot == 0:
        return x
    half = rot // 2
    inv = 1.0 / (d["theta"] ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]       # (S, half)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    rotated = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([xr * cos + rotated * sin, xp], -1)


def _block(x, w, d, quant):
    """One layer over one sequence x: (S, D)."""
    S = x.shape[0]
    D, H, KV, hd = d["d"], d["h"], d["kv"], d["hd"]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    pos = jnp.arange(S)
    h = _norm(x, w["ln1"], d)
    q = _mm(h, w["wq"].reshape(D, H * hd), quant).reshape(S, H, hd)
    k = _mm(h, w["wk"].reshape(D, KV * hd), quant).reshape(S, KV, hd)
    v = _mm(h, w["wv"].reshape(D, KV * hd), quant).reshape(S, KV, hd)
    q, k = _rope(q, pos, d), _rope(k, pos, d)
    g = H // KV
    qg = q.reshape(S // Q_BLOCK, Q_BLOCK, KV, g, hd)

    def attend(args):
        i, qb = args                                      # (Q, KV, g, hd)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) / math.sqrt(hd)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v)

    ctx = jax.lax.map(attend, (jnp.arange(S // Q_BLOCK), qg))
    x = x + _mm(ctx.reshape(S, H * hd), w["wo"].reshape(H * hd, D), quant)
    h = _norm(x, w["ln2"], d)
    m = jax.nn.silu(_mm(h, w["wg"], quant)) * _mm(h, w["wu"], quant)
    return x + _mm(m, w["wd"], quant)


@partial(jax.jit, static_argnames=("d", "quant"))
def _layer_step(x, base, layer, d, quant):
    d = dict(d)
    w = weights_layer(base, layer, d)
    return jax.lax.map(lambda xi: _block(xi, w, d, quant), x)


@partial(jax.jit, static_argnames=("d",))
def _embed_step(tokens, base, d):
    d = dict(d)
    return weights_embed(base, d).astype(jnp.float32)[tokens]


@partial(jax.jit, static_argnames=("d", "quant"))
def _head_step(x, rows, cols, base, d, quant):
    d = dict(d)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), weights_head(base, d))
    sel = x[rows, cols]                                   # (N, D)
    return _mm(_norm(sel, w["final"], d), w["head"], quant)


def logits(cfg: dict, seed: int, seqs: list[np.ndarray],
           wanted: list[np.ndarray], quant: str = "f32") -> list[np.ndarray]:
    """Logits (float32, host) at positions ``wanted[i]`` of sequence
    ``seqs[i]``, each sequence attended causally from its own start.
    Sequences are right-padded to one length; padding sits after every
    wanted position, so the causal mask keeps it out."""
    d = dims(cfg)
    key = tuple(sorted(d.items()))
    S = max(len(s) for s in seqs)
    S = -(-S // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    rows = np.concatenate([np.full(len(w), i) for i, w in enumerate(wanted)])
    cols = np.concatenate(wanted)
    base = base_key(seed)
    with jax.default_matmul_precision("highest"):
        x = _embed_step(jnp.asarray(toks), base, key)
        for layer in range(d["layers"]):
            x = _layer_step(x, base, jnp.int32(layer), key, quant)
        out = np.asarray(_head_step(x, jnp.asarray(rows), jnp.asarray(cols),
                                    base, key, quant))
    del x
    splits = np.cumsum([len(w) for w in wanted])[:-1]
    return np.split(out, splits)
