"""PowerSGD gradient compression: math invariants, training behaviour,
wire-bytes reduction in the compiled HLO."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.abi import make_abi
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.dist.mesh import make_platform_mesh
from repro.dist.sharding import ShardingRules
from repro.models import params as P
from repro.models.transformer import Model
from repro.train.compression import (_compressible, powersgd_init,
                                     powersgd_sync)
from repro.train.optimizer import OptConfig, adamw_init
from repro.train.train_step import TrainStepBuilder


def test_compressible_predicate():
    r = 4
    assert _compressible(jnp.zeros((256, 256)), r)
    assert not _compressible(jnp.zeros((256,)), r)          # 1D
    assert not _compressible(jnp.zeros((8, 8)), r)          # too small
    assert _compressible(jnp.zeros((64, 4, 32)), r)         # collapsed 3D


def test_rank_r_matrix_recovered_exactly():
    """A gradient that IS rank-r is transmitted losslessly (up to fp)."""
    key = jax.random.key(0)
    m, n, r = 64, 96, 4
    a = jax.random.normal(key, (m, r))
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, r))
    g = {"w": a @ b.T}
    st = powersgd_init(g, r)
    # a couple of power iterations refine Q
    out = g
    for _ in range(3):
        out, st = powersgd_sync(g, st, (), r)
    err = float(jnp.abs(out["w"] - g["w"]).max())
    assert err < 1e-3, err
    # and the error buffer is near zero
    assert float(jnp.abs(st["err"]["w"]).max()) < 1e-3


def test_error_feedback_conservation():
    """The EF identity: sum(transmitted) + error_k == k*G exactly
    (telescoping of e_t = (G + e_{t-1}) - out_t) -- nothing is ever
    silently dropped, only delayed."""
    key = jax.random.key(1)
    g = {"w": jax.random.normal(key, (64, 64))}
    st = powersgd_init(g, 2)
    total = jnp.zeros_like(g["w"])
    k = 10
    for _ in range(k):
        out, st = powersgd_sync(g, st, (), 2)
        total = total + out["w"]
    lhs = total + st["err"]["w"]
    rel = float(jnp.linalg.norm(lhs - k * g["w"])
                / jnp.linalg.norm(k * g["w"]))
    assert rel < 1e-4, rel


def test_training_with_powersgd_converges():
    cfg = get_config("llama3.2-3b").reduced()
    mesh = make_platform_mesh("local")
    m = Model(cfg, tp=1, act_dtype=jnp.float32)
    prm = P.materialize(m.param_defs(), jax.random.key(0))
    opt = OptConfig(lr=5e-3, warmup_steps=2, total_steps=100)
    abi = make_abi("host", mode="explicit", zero1=False,
                   grad_compression="float32", hierarchical=False,
                   compression="powersgd", rank=8)
    b = TrainStepBuilder(model=m, mesh=mesh, rules=ShardingRules.default(),
                         abi=abi, opt=opt)
    step = jax.jit(b.build())
    st = adamw_init(prm)
    comm = powersgd_init(prm, 8)
    st["comm"] = {"q": jax.tree.map(lambda a: a[None], comm["q"]),
                  "err": jax.tree.map(lambda a: a[None], comm["err"])}
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                  global_batch=8, seed=3))
    losses = []
    for i in range(20):
        batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        prm, st, metrics = step(prm, st, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses


MULTIDEV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.train.compression import powersgd_init, powersgd_sync
from repro.launch.analysis import parse_collectives

mesh = jax.make_mesh((4,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
g = {"w": jax.random.normal(jax.random.key(0), (512, 512))}
st = powersgd_init(g, 4)

def plain(gl):
    return jax.tree.map(lambda x: jax.lax.pmean(x, "data"), gl)

def psgd(gl, stl):
    return powersgd_sync(gl, stl, ("data",), 4)

from jax.sharding import PartitionSpec as Psp
sm_plain = jax.shard_map(plain, mesh=mesh, in_specs=(Psp(),),
                         out_specs=Psp(), axis_names={"data"},
                         check_vma=False)
sm_psgd = jax.shard_map(psgd, mesh=mesh, in_specs=(Psp(), Psp()),
                        out_specs=(Psp(), Psp()), axis_names={"data"},
                        check_vma=False)
co_plain = jax.jit(sm_plain).lower(g).compile()
co_psgd = jax.jit(sm_psgd).lower(g, st).compile()
w_plain = parse_collectives(co_plain.as_text()).wire_bytes
w_psgd = parse_collectives(co_psgd.as_text()).wire_bytes
# one numeric run: compressed mean of identical shards == rank-4 approx
out, _ = sm_psgd(g, st)
assert jnp.isfinite(out["w"]).all()
print("WIRE", w_plain, w_psgd)
# dense 512x512 AR vs two (512,4) pmeans: expect >30x reduction
assert w_psgd < w_plain / 30, (w_plain, w_psgd)
print("PSGD_WIRE_OK")
"""


def test_powersgd_cuts_wire_bytes_multidevice():
    r = subprocess.run([sys.executable, "-c", MULTIDEV],
                       capture_output=True, text=True,
                       env={**__import__("os").environ, "PYTHONPATH": "src"},
                       cwd=".")
    assert "PSGD_WIRE_OK" in r.stdout, r.stdout + r.stderr[-2000:]
