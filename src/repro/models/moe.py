"""Mixture-of-Experts: top-k routing with capacity-based scatter dispatch.

Dispatch strategy (XLA/SPMD-friendly, dry-run-compilable at 512 devices):

  1. router logits -> top-k experts + renormalised weights per token;
  2. position-in-expert via a cumsum over the (tokens, experts) one-hot;
     tokens beyond ``capacity = cf * T * k / E`` are dropped (GShard-style);
  3. scatter tokens into an (E, C, D) expert buffer -- the buffer is
     sharded E->model (expert parallelism) and C->data, so the scatter is
     where the MoE all-to-all happens, inserted by the SPMD partitioner;
  4. batched expert GEMMs einsum('ecd,edf->ecf') -- E model-sharded;
  5. gather back + weighted combine.

On TPU, step 3-4 would be replaced by a Pallas grouped-GEMM (megablocks
style); the XLA formulation here is the reference and the dry-run path.
FLOPs are proportional to *dispatched* tokens (cf * active), not to E --
this is what makes MODEL_FLOPS(active)/HLO_FLOPs meaningful for MoE archs.

Shared experts (DeepSeekMoE / Moonlight / Llama-4) run as a dense MLP branch
of width n_shared * expert_d_ff added to the routed output.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.layers import apply_mlp, mlp_defs
from repro.models.params import ParamDef


def moe_defs(cfg: ModelConfig) -> dict:
    D, E, F = cfg.d_model, cfg.n_experts, cfg.expert_d_ff
    d: dict = {
        "router": ParamDef((D, E), ("embed", None), "small_normal"),
        "experts": {
            "wg": ParamDef((E, D, F), ("experts", "embed", "mlp")),
            "wu": ParamDef((E, D, F), ("experts", "embed", "mlp")),
            "wd": ParamDef((E, F, D), ("experts", "mlp", "embed")),
        },
    }
    if cfg.n_shared_experts:
        d["shared"] = mlp_defs(cfg, d_ff=cfg.n_shared_experts * F)
    return d


def _router(p, x, cfg: ModelConfig):
    """x: (T, D) -> (idx (T,k), weight (T,k), aux_loss scalar)."""
    logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weight, idx = jax.lax.top_k(probs, cfg.top_k)
    weight = weight / jnp.maximum(weight.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing auxiliary loss
    E = cfg.n_experts
    me = probs.mean(0)                                               # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / idx.size
    aux = E * jnp.sum(me * ce)
    return idx, weight, aux


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(cfg.capacity_factor * n_tokens * cfg.top_k / cfg.n_experts)
    return max(8, -(-c // 8) * 8)   # round up to 8 for lane alignment


def moe_forward(p: dict, x: jax.Array, cfg: ModelConfig, constrain_fn=None):
    """x: (B,S,D) -> (B,S,D), aux_loss.

    ``constrain_fn(tensor, logical_axes)`` lets the caller inject sharding
    constraints (E->model, C->data) without this module knowing the mesh.
    """
    B, S, D = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.top_k
    C = capacity(cfg, T)
    xt = x.reshape(T, D)

    idx, weight, aux = _router(p, xt, cfg)                 # (T,k)

    # ---- position-in-expert (dropping beyond capacity) ---------------------
    flat_e = idx.reshape(-1)                               # (T*k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)    # (T*k, E)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)            # positions start at 0
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]   # (T*k,)
    keep = pos < C
    slot = flat_e * C + jnp.where(keep, pos, 0)            # (T*k,) in [0, E*C)

    # ---- dispatch: scatter into the (E*C, D) expert buffer -----------------
    src = jnp.repeat(xt, k, axis=0) * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((E * C, D), x.dtype).at[slot].add(
        src, mode="drop", indices_are_sorted=False, unique_indices=False
    )
    buf = buf.reshape(E, C, D)
    if constrain_fn is not None:
        buf = constrain_fn(buf, ("experts", "capacity", "embed"))

    # ---- expert compute: batched GEMMs, E sharded over model ---------------
    we = p["experts"]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, we["wg"].astype(x.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, we["wu"].astype(x.dtype))
    out_buf = jnp.einsum("ecf,efd->ecd", h, we["wd"].astype(x.dtype))
    if constrain_fn is not None:
        out_buf = constrain_fn(out_buf, ("experts", "capacity", "embed"))

    # ---- combine: gather back + weighted sum over k ------------------------
    gathered = out_buf.reshape(E * C, D)[slot]             # (T*k, D)
    gathered = gathered * (weight.reshape(-1)[:, None].astype(x.dtype)
                           * keep[:, None].astype(x.dtype))
    y = gathered.reshape(T, k, D).sum(axis=1)

    if cfg.n_shared_experts:
        y = y + apply_mlp(p["shared"], xt, cfg.mlp)
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# SPMD expert parallelism via shard_map (the production dispatch)
# ---------------------------------------------------------------------------
#
# GSPMD cannot partition a data-dependent scatter across the expert axis --
# left to propagation it REPLICATES the expert GEMMs on every device
# (observed: useful-flops fraction 0.007 on moonshot). The production path
# therefore makes the EP decomposition explicit with a *partial-manual*
# shard_map: manual over (pod, data, model), so that
#
#   * tokens stay local to their data shard (GShard "groups = data shards":
#     capacity is per-shard, no cross-data comm at all);
#   * each model shard owns E/tp experts and scatters ONLY its own experts'
#     tokens into a local (E_l, C, D) buffer (out-of-range slots dropped);
#   * expert GEMMs are plain local batched matmuls (MXU-shaped);
#   * the only communication is ONE psum over the model axis combining
#     routed partial outputs + the shared-expert partial sums -- the same
#     wire cost as the dense-FFN TP all-reduce it replaces.
#
# The Pallas grouped-GEMM kernel would slot in at the local einsum on TPU.

def moe_param_specs(cfg: ModelConfig, model_axis: str = "model") -> dict:
    """shard_map in_specs for the moe param subtree (matches moe_defs)."""
    d: dict = {
        "router": P(),
        "experts": {"wg": P(model_axis), "wu": P(model_axis),
                    "wd": P(model_axis)},
    }
    if cfg.n_shared_experts:
        d["shared"] = {"wg": P(None, model_axis), "wu": P(None, model_axis),
                       "wd": P(model_axis, None)}
    return d


def _moe_local(p: dict, x: jax.Array, cfg: ModelConfig, model_axis: str,
               batch_axes: tuple[str, ...], tp: int):
    """Per-device body. x: (B_local, S, D) -- batch already data-local.

    ``tp`` is the static model-axis size (from the mesh; lax.axis_size is
    not available on every supported jax)."""
    Bl, S, D = x.shape
    T = Bl * S
    E, k = cfg.n_experts, cfg.top_k
    el = E // tp
    off = jax.lax.axis_index(model_axis) * el
    C = capacity(cfg, T)                                   # per data shard
    xt = x.reshape(T, D)

    idx, weight, aux = _router(p, xt, cfg)                 # replicated math

    flat_e = idx.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = pos < C
    local = (flat_e >= off) & (flat_e < off + el)
    ok = keep & local
    # out-of-range slot for dropped/non-local tokens -> scatter mode "drop"
    slot = jnp.where(ok, (flat_e - off) * C + pos, el * C)

    src = jnp.repeat(xt, k, axis=0) * ok[:, None].astype(x.dtype)
    buf = jnp.zeros((el * C, D), x.dtype).at[slot].add(src, mode="drop")
    buf = buf.reshape(el, C, D)

    we = p["experts"]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, we["wg"].astype(x.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, we["wu"].astype(x.dtype))
    out_buf = jnp.einsum("ecf,efd->ecd", h, we["wd"].astype(x.dtype))

    gathered = jnp.take(out_buf.reshape(el * C, D), slot, axis=0,
                        mode="fill", fill_value=0)
    gathered = gathered * (weight.reshape(-1)[:, None].astype(x.dtype)
                           * ok[:, None].astype(x.dtype))
    y = gathered.reshape(T, k, D).sum(axis=1)

    if cfg.n_shared_experts:
        sh = p["shared"]
        hs = (jax.nn.silu(xt @ sh["wg"].astype(x.dtype))
              * (xt @ sh["wu"].astype(x.dtype)))           # (T, F_local)
        y = y + hs @ sh["wd"].astype(x.dtype)              # partial over F

    y = jax.lax.psum(y, model_axis)                        # THE one collective
    if batch_axes:
        aux = jax.lax.pmean(aux, tuple(batch_axes))
    return y.reshape(Bl, S, D), aux


def moe_forward_spmd(p: dict, x: jax.Array, cfg: ModelConfig, mesh: Mesh,
                     model_axis: str = "model",
                     batch_axes: tuple[str, ...] | None = None):
    """shard_map-wrapped EP dispatch; falls back to moe_forward when the
    mesh cannot shard it (E % tp != 0 or batch not divisible).

    ``batch_axes=None`` derives the data axes from the mesh; pass ``()``
    when calling from inside an outer shard_map that is already manual over
    the batch axes (the explicit-ABI train step).

    AXIS ORDER MATTERS: the batch dim everywhere else is constrained
    P(("pod","data")) -- the in/out specs here must use the SAME order or
    GSPMD inserts a full-batch reshard (observed: 2x21.5 GB all-gathers per
    MoE layer on the multipod mesh, 4x the cell's whole collective term)."""
    baxes = (tuple(a for a in ("pod", "data") if a in mesh.axis_names)
             if batch_axes is None else tuple(batch_axes))
    tp = mesh.shape.get(model_axis, 1)
    bdiv = 1
    for a in baxes:
        bdiv *= mesh.shape[a]
    if cfg.n_experts % tp or x.shape[0] % bdiv:
        return moe_forward(p, x, cfg)

    manual = set(baxes) | {model_axis}
    pspecs = moe_param_specs(cfg, model_axis)
    xspec = (P(baxes if len(baxes) > 1 else baxes[0]) if baxes else P())
    fn = jax.shard_map(
        lambda pl, xl: _moe_local(pl, xl, cfg, model_axis, baxes, tp),
        mesh=mesh,
        in_specs=(pspecs, xspec),
        out_specs=(xspec, P()),
        axis_names=manual,
        check_vma=False,
    )
    return fn(p, x)
