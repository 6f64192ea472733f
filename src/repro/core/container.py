"""Container: runtime instantiation of an EnvImage on a platform.

`docker run` analog. A Container binds the immutable image to

  * a concrete device mesh (the platform: local / pod / multipod),
  * resolved sharding rules (logical-axis table, FSDP/SP/ZeRO-1 toggles),
  * the collective ABI implementation named by the image,
  * compiled step functions (train / prefill / decode), obtained through
    the CompileCache (the import-problem fix),
  * a writable overlay directory (checkpoints, metrics, logs) -- the image
    is never mutated, many containers can share one image.

Input specs follow the assigned shape cell: ``input_specs()`` returns
weak-type-correct ShapeDtypeStructs (no allocation), which is what the
multi-pod dry-run lowers against.
"""

from __future__ import annotations

import json
import os
import uuid
from dataclasses import dataclass
from functools import cached_property, partial
from pathlib import Path
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_shape_cell
from repro.core.abi import CollectiveABI, abi_from_image_config
from repro.core.compile_cache import CompileCache
from repro.core.image import EnvImage
from repro.dist.mesh import PLATFORMS, batch_axes, make_platform_mesh
from repro.dist.sharding import ShardingRules, check_divisibility, safe_spec
from repro.models.config import ModelConfig, ShapeCell
from repro.models.params import abstract, materialize, shardings as def_shardings
from repro.models.transformer import Model
from repro.serve.serve_step import ServeStepBuilder
from repro.train.optimizer import OptConfig, adamw_init
from repro.train.train_step import TrainStepBuilder


_safe_spec = safe_spec  # shared with dist.sharding (exclude_axes-aware)


class Container:
    def __init__(self, image: EnvImage, platform: str | None = None,
                 overlay_root: str | os.PathLike | None = None,
                 compile_cache: CompileCache | None = None):
        self.image = image
        cfg = image.config()
        if cfg["arch"] is None:
            raise ValueError("image has no ARCH layer")
        self.settings: dict = dict(cfg.get("settings", {}))
        self.arch: ModelConfig = get_config(
            cfg["arch"]["name"], **cfg["arch"].get("overrides", {}))
        shape_cfg = dict(cfg.get("shape") or {})
        self.cell: ShapeCell | None = None
        if shape_cfg:
            base = get_shape_cell(shape_cfg.pop("name"))
            self.cell = base.scaled(**shape_cfg) if shape_cfg else base

        # platform: image default, overridable at run time (docker-run style)
        mesh_cfg = dict(cfg.get("mesh") or {"platform": "local"})
        self.platform = platform or mesh_cfg.get("platform", "local")
        self.mesh: Mesh = make_platform_mesh(self.platform)
        self.abi: CollectiveABI = abi_from_image_config(cfg)

        self.rules = ShardingRules.default(
            fsdp=bool(self.settings.get("fsdp", False)),
            seq_parallel=bool(self.settings.get("seq_parallel", False)),
        )
        extra_rules = self.settings.get("rules")
        if extra_rules:
            self.rules = self.rules.with_(**{
                k: (tuple(v) if isinstance(v, list) else v)
                for k, v in dict(extra_rules).items()
            })
        # ZeRO-1: optimizer state shards over the batch axes on 'embed' dims
        if self.abi.zero1:
            self.opt_rules = self.rules.with_(embed=("pod", "data"))
        else:
            self.opt_rules = self.rules

        tp = self.mesh.shape.get("model", 1)
        moe_impl = self.settings.get("moe_impl", "spmd")
        self.model = Model(
            self.arch, tp=tp,
            constrain=self._constrain,
            remat=str(self.settings.get("remat", "none")),
            act_dtype=jnp.dtype(cfg["precision"].get("compute", "bfloat16")),
            moe_mesh=self.mesh if (moe_impl == "spmd" and tp > 1
                                   and self.arch.n_experts) else None,
            mesh=self.mesh,
        )
        self.param_dtype = jnp.dtype(cfg["precision"].get("params", "float32"))
        self.cache_dtype = jnp.dtype(cfg["precision"].get("compute", "bfloat16"))
        self.opt = OptConfig(**self.settings.get("optimizer", {}))

        self.container_id = f"{image.short_digest}-{uuid.uuid4().hex[:8]}"
        self.overlay = (Path(overlay_root) if overlay_root
                        else Path(".stevedore") / "overlays") / self.container_id
        self.compile_cache = compile_cache
        self._metrics_path = self.overlay / "metrics.jsonl"
        # serve-step compile accounting, bucketed by dispatch class
        # ("prefill"/"decode"/"other" -> {hits, misses, seconds}); filled by
        # compile_serve_step, surfaced in SlotEngine.status()/`repro ps`
        self.serve_compile_stats: dict[str, dict] = {}

    # ------------------------------------------------------------------
    def _constrain(self, x, logical):
        spec = _safe_spec(x.shape, logical, self.mesh, self.rules)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    # -- parameters ---------------------------------------------------------
    @cached_property
    def param_defs(self):
        return self.model.param_defs()

    def param_shardings(self):
        return def_shardings(self.param_defs, self.mesh, self.rules)

    def opt_state_shardings(self):
        ps = def_shardings(self.param_defs, self.mesh, self.opt_rules)
        out = {"m": ps, "v": ps, "step": NamedSharding(self.mesh, P())}
        if self.param_dtype != jnp.float32:
            out["master"] = ps
        if self._powersgd_rank():
            from repro.dist.mesh import batch_axes
            baxes = batch_axes(self.mesh)
            sh0 = NamedSharding(self.mesh,
                                P(baxes if len(baxes) > 1 else baxes[0]))
            comm = self._comm_template(abstract_only=True)
            out["comm"] = jax.tree.map(lambda _: sh0, comm)
        return out

    def abstract_params(self):
        return abstract(self.param_defs, self.param_dtype)

    def abstract_opt_state(self):
        f32 = abstract(self.param_defs, jnp.float32)
        out = {"m": f32, "v": f32,
               "step": jax.ShapeDtypeStruct((), jnp.int32)}
        if self.param_dtype != jnp.float32:
            out["master"] = f32
        if self._powersgd_rank():
            out["comm"] = self._comm_template(abstract_only=True)
        return out

    def init_params(self, seed: int = 0):
        """Materialise params with the image's param shardings applied."""
        shs = self.param_shardings()
        init = jax.jit(
            lambda key: materialize(self.param_defs, key, self.param_dtype),
            out_shardings=shs)
        return init(jax.random.key(seed))

    def init_opt_state(self, params):
        from functools import partial
        init = partial(adamw_init,
                       with_master=self.param_dtype != jnp.float32)
        state = jax.jit(init, out_shardings={
            k: v for k, v in self.opt_state_shardings().items()
            if k != "comm"})(params)
        if self._powersgd_rank():
            from repro.train.compression import powersgd_init
            from repro.dist.mesh import batch_axes
            nsh = 1
            for a in batch_axes(self.mesh):
                nsh *= self.mesh.shape[a]
            comm = powersgd_init(jax.tree.map(lambda d: d, params),
                                 self._powersgd_rank())
            expand = lambda a: jnp.broadcast_to(a[None], (nsh, *a.shape))
            state["comm"] = {"q": jax.tree.map(expand, comm["q"]),
                             "err": jax.tree.map(expand, comm["err"])}
            sh = self.opt_state_shardings()["comm"]
            state["comm"] = jax.tree.map(jax.device_put, state["comm"], sh)
        return state

    def _powersgd_rank(self) -> int:
        if self.abi.options.get("compression") == "powersgd":
            return int(self.abi.options.get("rank", 16))
        return 0

    def _comm_template(self, abstract_only: bool = False):
        """Abstract comm-state tree: per-shard leading axis on q/err."""
        from repro.train.compression import _as_matrix, _compressible
        from repro.dist.mesh import batch_axes
        rank = self._powersgd_rank()
        nsh = 1
        for a in batch_axes(self.mesh):
            nsh *= self.mesh.shape[a]
        aparams = self.abstract_params()

        def q_leaf(p):
            if not _compressible(p, rank):
                return None
            n = int(np.prod(p.shape[1:]))
            return jax.ShapeDtypeStruct((nsh, n, rank), jnp.float32)

        def e_leaf(p):
            if not _compressible(p, rank):
                return None
            return jax.ShapeDtypeStruct((nsh, *p.shape), jnp.float32)

        return {"q": jax.tree.map(q_leaf, aparams),
                "err": jax.tree.map(e_leaf, aparams)}

    # -- input specs (ShapeDtypeStruct stand-ins; no allocation) -------------
    def input_specs(self, kind: str | None = None) -> dict:
        cell = self.cell
        if cell is None:
            raise ValueError("image has no SHAPE layer")
        kind = kind or cell.kind
        B, S = cell.global_batch, cell.seq_len
        fe_len = self.arch.frontend_len if self.arch.frontend else 0
        tok = jax.ShapeDtypeStruct((B, S - fe_len), jnp.int32)
        fe = (jax.ShapeDtypeStruct((B, fe_len, self.arch.d_model), self.cache_dtype)
              if fe_len else None)
        if kind == "train":
            batch = {"tokens": tok,
                     "labels": jax.ShapeDtypeStruct((B, S - fe_len), jnp.int32)}
            if fe is not None:
                batch["frontend_embeds"] = fe
            return {"batch": batch}
        if kind == "prefill":
            out = {"tokens": tok}
            if fe is not None:
                out["frontend_embeds"] = fe
            return out
        if kind == "decode":
            cache_defs = self.model.cache_defs(B, S, self.cache_dtype)
            cache = self._abstract_cache(cache_defs)
            return {
                "cache": cache,
                "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                "idx": jax.ShapeDtypeStruct((), jnp.int32),
            }
        raise ValueError(f"unknown step kind {kind!r}")

    def _abstract_cache(self, cache_defs):
        def leaf(d):
            # recurrent states are f32; kv/conv follow the compute dtype
            dt = jnp.float32 if d.shape and d.logical and (
                d.logical[-1] in ("rnn",) and len(d.shape) == 3
                or (len(d.shape) == 5 and d.logical[2] == "heads")
            ) else self.cache_dtype
            return jax.ShapeDtypeStruct(d.shape, dt)
        from repro.models.params import is_def
        return jax.tree.map(leaf, cache_defs, is_leaf=is_def)

    def input_shardings(self, specs) -> Any:
        """Tree of NamedShardings for an input_specs() tree."""
        def leaf_spec(x, logical):
            return NamedSharding(self.mesh,
                                 _safe_spec(x.shape, logical, self.mesh, self.rules))

        out: dict = {}
        for k, v in specs.items():
            if k == "batch":
                out[k] = {
                    kk: leaf_spec(vv, ("batch",) + (None,) * (vv.ndim - 1))
                    for kk, vv in v.items()
                }
            elif k in ("tokens", "frontend_embeds"):
                out[k] = leaf_spec(v, ("batch",) + (None,) * (v.ndim - 1))
            elif k == "idx":
                out[k] = NamedSharding(self.mesh, P())
            elif k == "cache":
                cache_defs = self.model.cache_defs(
                    self.cell.global_batch, self.cell.seq_len, self.cache_dtype)
                from repro.models.params import is_def
                out[k] = jax.tree.map(
                    lambda d: NamedSharding(
                        self.mesh,
                        _safe_spec(d.shape, d.logical, self.mesh, self.rules)),
                    cache_defs, is_leaf=is_def)
            else:
                raise KeyError(k)
        return out

    # -- step builders --------------------------------------------------------
    def train_step_fn(self) -> Callable:
        builder = TrainStepBuilder(
            model=self.model, mesh=self.mesh, rules=self.rules, abi=self.abi,
            opt=self.opt, microbatches=int(self.settings.get("microbatches", 1)))
        return builder.build()

    def prefill_fn(self, cache_len: int | None = None) -> Callable:
        b = ServeStepBuilder(self.model, self.mesh, self.rules)
        return b.build_prefill(cache_len or (self.cell.seq_len if self.cell else 0))

    def decode_fn(self) -> Callable:
        return ServeStepBuilder(self.model, self.mesh, self.rules).build_decode()

    # -- serving: slot-granular cache + compile-cached serve steps -------------
    def slot_cache_specs(self, n_slots: int, max_len: int):
        """Abstract KV/recurrent cache for a bank of ``n_slots`` independent
        request slots (each row one request, ``max_len`` positions)."""
        return self._abstract_cache(
            self.model.cache_defs(n_slots, max_len, self.cache_dtype))

    def slot_cache_shardings(self, n_slots: int, max_len: int):
        return self._cache_shardings(
            self.model.cache_defs(n_slots, max_len, self.cache_dtype))

    def init_slot_cache(self, n_slots: int, max_len: int):
        """Zero-initialised slot cache, placed per the image's shardings."""
        specs = self.slot_cache_specs(n_slots, max_len)
        sh = self.slot_cache_shardings(n_slots, max_len)
        return jax.tree.map(
            lambda s, nsh: jax.device_put(jnp.zeros(s.shape, s.dtype), nsh),
            specs, sh)

    # -- paged serving: global page pool shared by all slots -------------------
    def paged_cache_specs(self, n_pages: int, page_size: int):
        """Abstract paged KV pool: per attention layer (n_kv, n_pages,
        page_size, hd); slots address it through the host PagePool's table."""
        return self._abstract_cache(
            self.model.paged_cache_defs(n_pages, page_size, self.cache_dtype))

    def paged_cache_shardings(self, n_pages: int, page_size: int):
        return self._cache_shardings(
            self.model.paged_cache_defs(n_pages, page_size, self.cache_dtype))

    def init_paged_cache(self, n_pages: int, page_size: int):
        """Zero-initialised page pool, placed per the image's shardings."""
        specs = self.paged_cache_specs(n_pages, page_size)
        sh = self.paged_cache_shardings(n_pages, page_size)
        return jax.tree.map(
            lambda s, nsh: jax.device_put(jnp.zeros(s.shape, s.dtype), nsh),
            specs, sh)

    def _cache_shardings(self, cache_defs):
        from repro.models.params import is_def
        return jax.tree.map(
            lambda d: NamedSharding(self.mesh, _safe_spec(
                d.shape, d.logical, self.mesh, self.rules)),
            cache_defs, is_leaf=is_def)

    def _batch_sharding(self, shape):
        return NamedSharding(self.mesh, _safe_spec(
            shape, ("batch",) + (None,) * (len(shape) - 1), self.mesh,
            self.rules))

    def lower_serve_step(self, kind: str, *, batch: int | None = None,
                         prompt_len: int | None = None,
                         cache_len: int | None = None,
                         gen_steps: int | None = None,
                         n_pages: int | None = None,
                         page_size: int | None = None,
                         max_pages: int | None = None,
                         frontend_len: int | None = None,
                         prefix_len: int | None = None,
                         per_row: bool | None = None, donate: bool = True):
        """jit + lower a serving step at arbitrary (non-cell) shapes.

        kinds: ``prefill`` (B,P -> last_logits+cache), ``prefill_slot``
        (B,P bucket + lengths -> first tokens + cache; ``frontend_len``
        adds a modality-prefix buffer + per-row prefix lengths ahead of the
        prompt), ``decode_slots`` (slot bank, per-row positions),
        ``generate`` (scanned greedy loop; ``per_row`` makes the start
        position a (B,) vector for mixed-length waves), plus the
        ``*_paged`` variants (KV as a global page pool + per-slot page
        table; see kernels/paged_attention).
        All carry explicit in/out shardings -- replicated-output caches
        would all-gather the full KV (see lower_step NOTE).
        """
        from repro.models.layers import padded_vocab
        b = ServeStepBuilder(self.model, self.mesh, self.rules)
        pspec = self.param_shardings()
        rep = NamedSharding(self.mesh, P())
        vp = padded_vocab(self.arch.vocab_size)
        aparams = self.abstract_params()
        tok = jnp.int32

        if kind == "prefill":
            fn = b.build_prefill(cache_len)
            toks = jax.ShapeDtypeStruct((batch, prompt_len), tok)
            cache_sh = self._cache_shardings(
                self.model.cache_defs(batch, cache_len, self.cache_dtype))
            logits_sh = NamedSharding(self.mesh, _safe_spec(
                (batch, vp), ("batch", "vocab"), self.mesh, self.rules))
            jitted = jax.jit(
                fn, in_shardings=(pspec, self._batch_sharding(toks.shape)),
                out_shardings=(logits_sh, cache_sh))
            return jitted.lower(aparams, toks)
        if kind == "prefill_slot":
            B = batch or 1
            fe_len = frontend_len or 0
            fn = b.build_prefill_slot(cache_len, fe_len)
            toks = jax.ShapeDtypeStruct((B, prompt_len), tok)
            # B=1 (orchestrator slot prefill): scalar length, replicated
            # outputs; B>1 (static wave prefill): per-row length vectors
            length = (jax.ShapeDtypeStruct((B,), tok) if B > 1
                      else jax.ShapeDtypeStruct((), tok))
            len_sh = self._batch_sharding((B,)) if B > 1 else rep
            first_sh = self._batch_sharding((B,)) if B > 1 else rep
            cache_sh = self._cache_shardings(
                self.model.cache_defs(B, cache_len, self.cache_dtype))
            args = [aparams, toks, length]
            arg_sh = [pspec, self._batch_sharding(toks.shape), len_sh]
            if fe_len:
                fe = jax.ShapeDtypeStruct((B, fe_len, self.arch.d_model),
                                          self.cache_dtype)
                args += [fe, length]
                arg_sh += [self._batch_sharding(fe.shape), len_sh]
            jitted = jax.jit(fn, in_shardings=tuple(arg_sh),
                             out_shardings=(first_sh, cache_sh))
            return jitted.lower(*args)
        if kind == "decode_slots":
            fn = b.build_decode_slots()
            cache = self.slot_cache_specs(batch, cache_len)
            cache_sh = self.slot_cache_shardings(batch, cache_len)
            toks = jax.ShapeDtypeStruct((batch, 1), tok)
            pos = jax.ShapeDtypeStruct((batch,), tok)
            tok_sh = self._batch_sharding(toks.shape)
            jitted = jax.jit(
                fn,
                in_shardings=(pspec, cache_sh, tok_sh,
                              self._batch_sharding(pos.shape)),
                out_shardings=(self._batch_sharding(pos.shape), cache_sh),
                donate_argnums=(1,) if donate else (),
            )
            return jitted.lower(aparams, cache, toks, pos)
        if kind == "decode_chunk":
            fn = b.build_decode_chunk(gen_steps)
            cache = self.slot_cache_specs(batch, cache_len)
            cache_sh = self.slot_cache_shardings(batch, cache_len)
            toks = jax.ShapeDtypeStruct((batch, 1), tok)
            pos = jax.ShapeDtypeStruct((batch,), tok)
            tok_sh = self._batch_sharding(toks.shape)
            pos_sh = self._batch_sharding(pos.shape)
            jitted = jax.jit(
                fn,
                in_shardings=(pspec, cache_sh, tok_sh, pos_sh),
                out_shardings=(self._batch_sharding((batch, gen_steps)),
                               tok_sh, pos_sh, cache_sh),
                donate_argnums=(1,) if donate else (),
            )
            return jitted.lower(aparams, cache, toks, pos)
        if kind == "prefill_slot_paged":
            fe_len = frontend_len or 0
            pfx = prefix_len or 0
            fn = b.build_prefill_slot_paged(prompt_len, page_size, fe_len,
                                            pfx)
            toks = jax.ShapeDtypeStruct((1, prompt_len), tok)
            length = jax.ShapeDtypeStruct((), tok)
            if pfx:
                # prefix-registry hit: suffix-only prefill reading the
                # matched chain's pages straight out of the live pool
                # (undonated). pfx may end mid-page (radix partial match):
                # the page list rounds UP to cover the boundary page, and
                # the output cache covers the merged front-partial rows too
                frac = pfx % page_size
                np_ = -(-(frac + prompt_len) // page_size)
                cache_sh = self._cache_shardings(
                    self.model.paged_cache_defs(np_, page_size,
                                                self.cache_dtype))
                pool = self.paged_cache_specs(n_pages, page_size)
                pool_sh = self.paged_cache_shardings(n_pages, page_size)
                pages = jax.ShapeDtypeStruct((-(-pfx // page_size),), tok)
                jitted = jax.jit(
                    fn,
                    in_shardings=(pspec, pool_sh,
                                  self._batch_sharding(toks.shape), rep, rep),
                    out_shardings=(rep, cache_sh))
                return jitted.lower(aparams, pool, toks, length, pages)
            np_ = -(-(prompt_len + fe_len) // page_size)
            # the page-major small cache reuses the pool defs at np_ pages
            cache_sh = self._cache_shardings(
                self.model.paged_cache_defs(np_, page_size, self.cache_dtype))
            args = [aparams, toks, length]
            arg_sh = [pspec, self._batch_sharding(toks.shape), rep]
            if fe_len:
                fe = jax.ShapeDtypeStruct((1, fe_len, self.arch.d_model),
                                          self.cache_dtype)
                args += [fe, length]
                arg_sh += [self._batch_sharding(fe.shape), rep]
            jitted = jax.jit(fn, in_shardings=tuple(arg_sh),
                             out_shardings=(rep, cache_sh))
            return jitted.lower(*args)
        if kind in ("decode_slots_paged", "decode_chunk_paged"):
            chunked = kind == "decode_chunk_paged"
            fn = (b.build_decode_chunk_paged(gen_steps) if chunked
                  else b.build_decode_slots_paged())
            cache = self.paged_cache_specs(n_pages, page_size)
            cache_sh = self.paged_cache_shardings(n_pages, page_size)
            toks = jax.ShapeDtypeStruct((batch, 1), tok)
            pos = jax.ShapeDtypeStruct((batch,), tok)
            table = jax.ShapeDtypeStruct((batch, max_pages), tok)
            tok_sh = self._batch_sharding(toks.shape)
            pos_sh = self._batch_sharding(pos.shape)
            table_sh = self._batch_sharding(table.shape)
            out_sh = ((self._batch_sharding((batch, gen_steps)),
                       tok_sh, pos_sh, cache_sh) if chunked
                      else (pos_sh, cache_sh))
            jitted = jax.jit(
                fn,
                in_shardings=(pspec, cache_sh, tok_sh, pos_sh, table_sh),
                out_shardings=out_sh,
                donate_argnums=(1,) if donate else (),
            )
            return jitted.lower(aparams, cache, toks, pos, table)
        if kind == "generate":
            fn = b.build_generate_loop(gen_steps)
            cache = self._abstract_cache(
                self.model.cache_defs(batch, cache_len, self.cache_dtype))
            cache_sh = self._cache_shardings(
                self.model.cache_defs(batch, cache_len, self.cache_dtype))
            first = jax.ShapeDtypeStruct((batch, 1), tok)
            # per_row: mixed-length waves decode from per-row start
            # positions (decode_attn already takes (B,) idx vectors)
            start = (jax.ShapeDtypeStruct((batch,), tok) if per_row
                     else jax.ShapeDtypeStruct((), tok))
            start_sh = self._batch_sharding((batch,)) if per_row else rep
            out_sh = self._batch_sharding((batch, gen_steps))
            jitted = jax.jit(
                fn,
                in_shardings=(pspec, cache_sh,
                              self._batch_sharding(first.shape), start_sh),
                out_shardings=(out_sh, cache_sh),
                donate_argnums=(1,) if donate else (),
            )
            return jitted.lower(aparams, cache, first, start)
        raise ValueError(f"unknown serve step kind {kind!r}")

    def _serve_cache_digest(self) -> str:
        """Cache identity for serve steps: only the image config sections
        that determine the lowered computation (arch/mesh/precision/
        settings). Keying on the raw image digest would defeat the rollover
        warm-start -- a release that only re-points a tag at an image with
        new LABEL/COLLECTIVES layers would always miss despite lowering the
        byte-identical serve step."""
        import hashlib
        cfg = self.image.config()
        rel = {k: cfg.get(k) for k in ("arch", "mesh", "precision",
                                       "settings")}
        return hashlib.sha256(
            json.dumps(rel, sort_keys=True, default=str).encode()).hexdigest()

    def compile_serve_step(self, kind: str, **shapes):
        """lower+compile a serve step through the CompileCache when attached.

        This is the import-problem fix applied to serving: every replica of
        a Pod, a rerun of the same driver, or a rollover to a re-tagged
        image whose serving-relevant layers are unchanged deserializes the
        executable instead of re-tracing (see _serve_cache_digest).
        """
        from repro.orchestrator.obs.tracing import span
        from repro.serve.serve_step import dispatch_class
        acct = self.serve_compile_stats.setdefault(
            dispatch_class(kind), {"hits": 0, "misses": 0, "seconds": 0.0})
        with span("compile", step=kind) as sp:
            if self.compile_cache is None:
                import time
                t0 = time.perf_counter()
                exe = self.lower_serve_step(kind, **shapes).compile()
                acct["misses"] += 1
                acct["seconds"] += time.perf_counter() - t0
                sp.set_metadata(hit=False)
                return exe
            sig = ",".join(f"{k}={v}" for k, v in sorted(shapes.items())
                           if v is not None)
            key = self.compile_cache.key(
                image_digest=self._serve_cache_digest(),
                step_kind=f"serve:{kind}[{sig}]",
                mesh=self.mesh, args_tree=None)
            stats = self.compile_cache.stats
            hits0, miss0 = stats.hits_l1 + stats.hits_l2, stats.misses
            exe = self.compile_cache.get_or_build(
                key, lambda: self.lower_serve_step(kind, **shapes))
            hits = (stats.hits_l1 + stats.hits_l2) - hits0
            acct["hits"] += hits
            acct["misses"] += stats.misses - miss0
            acct["seconds"] += stats.last_seconds
            sp.set_metadata(hit=hits > 0)
            return exe

    # -- lowering (the dry-run entry) ------------------------------------------
    def lower_step(self, kind: str | None = None, donate: bool = True):
        """jit + lower the step for this image's shape cell. Returns Lowered."""
        kind = kind or (self.cell.kind if self.cell else "train")
        specs = self.input_specs(kind)
        in_sh = self.input_shardings(specs)
        pspec = self.param_shardings()

        if kind == "train":
            step = self.train_step_fn()
            ospec = self.opt_state_shardings()
            rep = NamedSharding(self.mesh, P())
            mspec = {"loss": rep, "aux_loss": rep, "grad_norm": rep, "lr": rep}
            jitted = jax.jit(
                step,
                in_shardings=(pspec, ospec, in_sh["batch"]),
                out_shardings=(pspec, ospec, mspec),
                donate_argnums=(0, 1) if donate else (),
            )
            return jitted.lower(self.abstract_params(),
                                self.abstract_opt_state(), specs["batch"])
        if kind == "prefill":
            fn = self.prefill_fn()
            args = [self.abstract_params(), specs["tokens"]]
            arg_sh = [pspec, in_sh["tokens"]]
            if "frontend_embeds" in specs:
                args.append(specs["frontend_embeds"])
                arg_sh.append(in_sh["frontend_embeds"])
            # outputs: (last_logits, cache) -- cache MUST come out sharded
            # (replicated-output caches would all-gather 100s of GB)
            cell = self.cell
            cache_defs = self.model.cache_defs(cell.global_batch,
                                               cell.seq_len, self.cache_dtype)
            from repro.models.params import is_def
            cache_out_sh = jax.tree.map(
                lambda d: NamedSharding(self.mesh, _safe_spec(
                    d.shape, d.logical, self.mesh, self.rules)),
                cache_defs, is_leaf=is_def)
            from repro.models.layers import padded_vocab
            logits_sh = NamedSharding(self.mesh, _safe_spec(
                (cell.global_batch, padded_vocab(self.arch.vocab_size)),
                ("batch", "vocab"), self.mesh, self.rules))
            jitted = jax.jit(fn, in_shardings=tuple(arg_sh),
                             out_shardings=(logits_sh, cache_out_sh))
            return jitted.lower(*args)
        if kind == "decode":
            fn = self.decode_fn()
            cache_sh = in_sh["cache"]
            jitted = jax.jit(
                fn,
                in_shardings=(pspec, cache_sh, in_sh["tokens"], in_sh["idx"]),
                out_shardings=(NamedSharding(self.mesh, P()), cache_sh),
                donate_argnums=(1,) if donate else (),
            )
            return jitted.lower(self.abstract_params(), specs["cache"],
                                specs["tokens"], specs["idx"])
        raise ValueError(kind)

    def lower_unit_probe(self, si: int, kind: str | None = None):
        """Lower the per-unit cost probe for stage ``si`` (scan correction).

        Returns (lowered, count) where count is the stage's scan trip count.
        """
        kind = kind or (self.cell.kind if self.cell else "train")
        st = self.model.stages[si]
        cell = self.cell
        B = cell.global_batch
        S = cell.seq_len if kind != "decode" else 1
        D = self.arch.d_model
        act = self.model.act_dtype

        udefs = self.model.unit_param_defs(si)
        u_abs = abstract(udefs, self.param_dtype)
        u_sh = def_shardings(udefs, self.mesh, self.rules)
        x_abs = jax.ShapeDtypeStruct((B, S, D), act)
        x_sh = NamedSharding(self.mesh, _safe_spec(
            (B, S, D), ("batch", "seq", "embed"), self.mesh, self.rules))
        probe = self.model.unit_probe(si, kind)

        # NOTE: probe OUTPUTS carry explicit shardings -- otherwise XLA may
        # choose replicated outputs, paying a full-batch all-gather per unit
        # that the real (scanned) module never pays; this inflated the
        # collective term ~5-10x before it was caught (EXPERIMENTS.md §Perf).
        rep = NamedSharding(self.mesh, P())
        from repro.models.params import is_def

        def _cache_sh(cdefs):
            return jax.tree.map(
                lambda d: NamedSharding(self.mesh, _safe_spec(
                    d.shape, d.logical, self.mesh, self.rules)),
                cdefs, is_leaf=is_def)

        if kind in ("train", "prefill"):
            pos_abs = jax.ShapeDtypeStruct((1, S), jnp.int32)
            if kind == "train":
                out_sh = (u_sh, x_sh)
            else:
                ys_defs = self.model.unit_cache_defs(si, B, S,
                                                     self.cache_dtype)
                out_sh = (x_sh, rep, _cache_sh(ys_defs))
            jitted = jax.jit(probe, in_shardings=(u_sh, x_sh, rep),
                             out_shardings=out_sh)
            return jitted.lower(u_abs, x_abs, pos_abs), st.count
        if kind == "decode":
            cdefs = self.model.unit_cache_defs(si, B, cell.seq_len,
                                               self.cache_dtype)
            c_abs = self._abstract_cache(cdefs)
            c_sh = _cache_sh(cdefs)
            idx_abs = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(
                probe,
                in_shardings=(u_sh, c_sh, x_sh, rep),
                out_shardings=(x_sh, c_sh))
            return jitted.lower(u_abs, c_abs, x_abs, idx_abs), st.count
        raise ValueError(kind)

    def compile_step(self, kind: str | None = None):
        """lower+compile, via the CompileCache when one is attached."""
        kind = kind or (self.cell.kind if self.cell else "train")
        if self.compile_cache is None:
            return self.lower_step(kind).compile()
        key = self.compile_cache.key(
            image_digest=self.image.digest, step_kind=kind, mesh=self.mesh,
            args_tree=self.input_specs(kind))
        return self.compile_cache.get_or_build(
            key, lambda: self.lower_step(kind))

    # -- overlay (writable layer) ----------------------------------------------
    def ensure_overlay(self) -> Path:
        self.overlay.mkdir(parents=True, exist_ok=True)
        meta = self.overlay / "container.json"
        if not meta.exists():
            meta.write_text(json.dumps({
                "image": self.image.digest,
                "platform": self.platform,
                "arch": self.arch.name,
                "cell": self.cell.name if self.cell else None,
                "abi": self.abi.describe(),
            }, indent=2))
        return self.overlay

    def log_metrics(self, step: int, metrics: dict) -> None:
        self.ensure_overlay()
        rec = {"step": step}
        for k, v in metrics.items():
            rec[k] = float(v) if hasattr(v, "__float__") or isinstance(
                v, (int, float, np.floating)) else v
        with open(self._metrics_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
