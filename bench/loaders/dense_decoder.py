"""Load the reference's seeded weights into the program's parameter tree.

The tree is the one the program's dense decoder declares: ``embed``
(``tokens`` (Vp, D), ``head`` (D, Vp)), ``final_norm`` and one scanned
stage ``stage0/b0`` with every layer stacked on the leading axis. The
rotary dims of ``wq``/``wk`` are reordered from the published
rotate_half layout to the program's interleaved pairs, as a checkpoint
converter would: q.k is unchanged, so both compute the same model.

The whole tree is made on the device in one jitted call from the seed, in
the program's parameter dtype. A tree that does not match the program's
declared shapes and dtypes is an error, not a guess.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def rope_order(hd: int, rot: int) -> np.ndarray:
    """Program column c reads published column order[c]: pairs (2j, 2j+1)
    of the program are (j, j + rot/2) of rotate_half."""
    half = rot // 2
    pairs = np.stack([np.arange(half), np.arange(half) + half], 1).reshape(-1)
    return np.concatenate([pairs, np.arange(rot, hd)]).astype(np.int32)


def program_params(ref, cfg: dict, seed: int, abstract, shardings):
    """``ref``: the reference module; ``abstract``/``shardings``: the
    program's declared parameter tree (ShapeDtypeStructs) and placement."""
    d = ref.dims(cfg)
    order = jnp.asarray(rope_order(d["hd"], d["rot"]))
    vp = abstract["embed"]["tokens"].shape[0]
    pad = vp - d["vocab"]

    def build(base):
        lw = jax.vmap(lambda i: ref.weights_layer(base, i, d))(
            jnp.arange(d["layers"]))
        block = {
            "ln1": lw["ln1"],
            "attn": {"wq": lw["wq"][..., order], "wk": lw["wk"][..., order],
                     "wv": lw["wv"], "wo": lw["wo"]},
            "ln2": lw["ln2"],
            "mlp": {"wg": lw["wg"], "wu": lw["wu"], "wd": lw["wd"]},
        }
        head = ref.weights_head(base, d)
        tree = {
            "embed": {"tokens": jnp.pad(ref.weights_embed(base, d),
                                        ((0, pad), (0, 0))),
                      "head": jnp.pad(head["head"], ((0, 0), (0, pad)))},
            "final_norm": head["final"],
            "stage0": {"b0": block},
        }
        return jax.tree.map(lambda a, s: a.astype(s.dtype), tree, abstract)

    base = ref.base_key(seed)
    made = jax.eval_shape(build, base)
    want = jax.tree.map(lambda s: (s.shape, s.dtype), abstract)
    got = jax.tree.map(lambda s: (s.shape, s.dtype), made)
    if jax.tree.structure(want) != jax.tree.structure(got) or want != got:
        raise SystemExit(f"the program's parameter tree {want} is not the "
                         f"dense decoder this loader makes: {got}")
    return jax.jit(build, out_shardings=shardings)(base)
