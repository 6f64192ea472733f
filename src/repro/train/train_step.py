"""Train-step builders: implicit (pjit/GSPMD) and explicit (shard_map) paths.

Two ABI-compatible step builders (the whole point of core/abi.py):

* ``implicit``  -- plain jit: the SPMD partitioner inserts gradient
  collectives. The ``generic`` ABI uses this with replicated optimizer
  states (flat fp32 all-reduce: the "container MPICH"). ZeRO-1 (part of
  the ``host`` ABI) is also expressed here purely through *optimizer-state
  shardings*: m/v shard over batch axes, so XLA rewrites the gradient
  all-reduce into reduce-scatter + (param) all-gather.

* ``explicit``  -- shard_map manual over the batch axes, ``auto`` over the
  model axis: gradients are synced by ``abi.grad_sync`` (bf16 wire dtype,
  hierarchical pod-then-ICI reduction). TP stays with GSPMD inside the
  auto axis. This is the "Cray MPI" path.

Both produce bit-compatible *interfaces*: (params, opt_state, batch, rng) ->
(params, opt_state, metrics). Swapping never touches model code.

Gradient accumulation: ``microbatches > 1`` scans over batch slices,
accumulating f32 grads (bytes on the wire unchanged, peak activation
memory divided by the microbatch count).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.abi import CollectiveABI
from repro.dist.mesh import batch_axes
from repro.dist.sharding import ShardingRules, constrain
from repro.train.compression import powersgd_sync
from repro.models.config import ModelConfig
from repro.models.layers import padded_vocab
from repro.models.transformer import Model
from repro.train.optimizer import OptConfig, adamw_update

AUX_LOSS_WEIGHT = 0.01


def cross_entropy(logits: jax.Array, labels: jax.Array, vocab_size: int,
                  mask: jax.Array | None = None):
    """logits: (B,S,Vp) with physical padding beyond vocab_size; labels (B,S).

    Padded vocab columns are masked to -inf so the partition function is
    exact w.r.t. the canonical vocabulary."""
    vp = logits.shape[-1]
    lg = logits.astype(jnp.float32)
    if vp != vocab_size:
        col = jnp.arange(vp) >= vocab_size
        lg = jnp.where(col[None, None, :], -1e30, lg)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        m = mask.astype(jnp.float32)
        return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
    return nll.mean()


@dataclass
class TrainStepBuilder:
    model: Model
    mesh: Mesh
    rules: ShardingRules
    abi: CollectiveABI
    opt: OptConfig
    microbatches: int = 1

    # -- loss ------------------------------------------------------------
    def _loss(self, params, batch):
        cfg = self.model.cfg
        fe = batch.get("frontend_embeds")
        logits, aux = self.model.forward(params, batch["tokens"],
                                         frontend_embeds=fe)
        labels = batch["labels"]
        if fe is not None:
            # frontend prefix carries no LM loss; labels cover token positions
            logits = logits[:, fe.shape[1]:]
        loss = cross_entropy(logits, labels, cfg.vocab_size,
                             batch.get("loss_mask"))
        return loss + AUX_LOSS_WEIGHT * aux, (loss, aux)

    def _grads(self, params, batch):
        """(possibly microbatched) value-and-grad; returns f32 grad tree."""
        if self.microbatches == 1:
            (_, (loss, aux)), grads = jax.value_and_grad(
                self._loss, has_aux=True)(params, batch)
            return grads, loss, aux

        n = self.microbatches

        def slice_mb(x, i):
            mb = x.shape[0] // n
            return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)

        def body(carry, i):
            gacc, lacc, aacc = carry
            mb = jax.tree.map(lambda x: slice_mb(x, i), batch)
            (_, (loss, aux)), g = jax.value_and_grad(
                self._loss, has_aux=True)(params, mb)
            gacc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gacc, g)
            return (gacc, lacc + loss, aacc + aux), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (g, loss, aux), _ = jax.lax.scan(
            body, (g0, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            jnp.arange(n))
        g = jax.tree.map(lambda x: x / n, g)
        return g, loss / n, aux / n

    # -- implicit (pjit) path ------------------------------------------------
    def build_implicit(self) -> Callable:
        def step(params, opt_state, batch):
            grads, loss, aux = self._grads(params, batch)
            new_params, new_state, om = adamw_update(params, grads, opt_state,
                                                     self.opt)
            metrics = {"loss": loss, "aux_loss": aux, **om}
            return new_params, new_state, metrics

        return step

    # -- explicit (shard_map) path --------------------------------------------
    def build_explicit(self) -> Callable:
        """Manual over the batch axes, auto over model.

        NOTE: params are replicated across the manual axes inside the region,
        so this path composes with TP but NOT with FSDP/ZeRO param sharding --
        it is the right shape for models whose (params+opt)/TP fits HBM
        (the paper's Fig.3-style runs); large models take the implicit ZeRO-1
        path instead (see build()).
        """
        import copy

        from repro.dist.sharding import safe_spec

        baxes = batch_axes(self.mesh)
        manual = set(baxes)
        bspec = P(baxes if len(baxes) > 1 else baxes[0])

        # model clone whose sharding constraints never mention manual axes
        mesh, rules = self.mesh, self.rules
        excl = tuple(manual)

        def local_constrain(x, logical):
            spec = safe_spec(x.shape, logical, mesh, rules, exclude_axes=excl)
            return jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(mesh, spec))

        local_model = copy.copy(self.model)
        local_model.constrain = local_constrain
        if getattr(local_model, "moe_mesh", None) is not None:
            # inner EP shard_map may only be manual over the (still-auto)
            # model axis; data is already manual out here
            local_model.moe_batch_axes = ()
        local_self = copy.copy(self)
        local_self.model = local_model

        use_psgd = self.abi.options.get("compression") == "powersgd"
        rank = int(self.abi.options.get("rank", 16))

        def local_step(params, opt_state, batch):
            comm = opt_state.get("comm")
            opt_core = {k: v for k, v in opt_state.items() if k != "comm"}
            grads, loss, aux = local_self._grads(params, batch)
            if use_psgd and comm is not None:
                # comm leaves carry a leading per-shard axis (size 1 locally:
                # the error buffer is PER-REPLICA state, unlike params)
                comm_local = {
                    "q": jax.tree.map(lambda a: a[0], comm["q"]),
                    "err": jax.tree.map(lambda a: a[0], comm["err"]),
                    "rank": rank,
                }
                grads, comm_local = powersgd_sync(grads, comm_local, baxes,
                                                  rank)
                comm = {
                    "q": jax.tree.map(lambda a: a[None], comm_local["q"]),
                    "err": jax.tree.map(lambda a: a[None], comm_local["err"]),
                }
            else:
                # the ABI swap point: wire dtype + topology live here
                grads = self.abi.grad_sync(grads, baxes)
            loss = jax.lax.pmean(loss, tuple(baxes))
            aux = jax.lax.pmean(aux, tuple(baxes))
            new_params, new_state, om = adamw_update(params, grads, opt_core,
                                                     self.opt)
            if comm is not None:
                new_state["comm"] = comm
            metrics = {"loss": loss, "aux_loss": aux, **om}
            return new_params, new_state, metrics

        rep = P()  # params/opt replicated over the manual (batch) axes
        shard0 = P(baxes if len(baxes) > 1 else baxes[0])

        def ospec_for(opt_state):
            def spec(path_is_comm, tree):
                return jax.tree.map(
                    lambda _: shard0 if path_is_comm else rep, tree)
            out = {k: spec(k == "comm", v) for k, v in opt_state.items()}
            return out

        def step(params, opt_state, batch):
            pspec = jax.tree.map(lambda _: rep, params)
            ospec = ospec_for(opt_state)
            bspec_tree = jax.tree.map(lambda _: bspec, batch)
            mspec = {"loss": rep, "aux_loss": rep, "grad_norm": rep, "lr": rep}
            return jax.shard_map(
                local_step, mesh=self.mesh,
                in_specs=(pspec, ospec, bspec_tree),
                out_specs=(pspec, ospec, mspec),
                check_vma=False,
                axis_names=manual,
            )(params, opt_state, batch)

        return step

    def build(self) -> Callable:
        """ABI -> step-path binding.

        generic        -> implicit (flat fp32 AR, replicated opt)
        host (default) -> implicit + ZeRO-1 (RS+AG via opt-state shardings;
                          composes with FSDP for the big models)
        host mode=explicit -> shard_map path: bf16 wire + hierarchical
                          pod-aware reductions (small/medium models whose
                          params fit replicated across the batch axes)
        """
        if self.abi.options.get("mode") == "explicit":
            return self.build_explicit()
        return self.build_implicit()
