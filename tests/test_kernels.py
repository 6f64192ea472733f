"""Per-kernel validation: shape/dtype sweeps, interpret=True vs ref.py oracle
(deliverable c, kernel part)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ops import flash_attention, pick_blocks
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.matmul.ops import matmul
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.rglru_scan.kernel import rglru_scan_pallas
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_ref
from repro.kernels.ssd.kernel import ssd_pallas
from repro.kernels.ssd.ref import ssd_ref

pytestmark = pytest.mark.kernels


def _tol(dt):
    return 3e-2 if dt == jnp.bfloat16 else 3e-5


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FA_CASES = [
    # B, Hq, Hkv, Sq, Sk, d, causal, window, dtype
    (2, 4, 2, 128, 128, 64, True, 0, jnp.float32),
    (1, 8, 1, 256, 256, 32, True, 0, jnp.float32),     # MQA
    (2, 4, 4, 128, 256, 64, True, 64, jnp.float32),    # window + kv>q
    (1, 2, 2, 128, 128, 128, False, 0, jnp.float32),   # bidirectional
    (1, 4, 2, 256, 256, 64, True, 0, jnp.bfloat16),
    (1, 2, 1, 64, 192, 128, True, 128, jnp.bfloat16),
]


@pytest.mark.parametrize("case", FA_CASES, ids=str)
def test_flash_attention_vs_ref(case):
    B, Hq, Hkv, Sq, Sk, d, causal, window, dt = case
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, d), dt)
    k = jax.random.normal(ks[1], (B, Hkv, Sk, d), dt)
    v = jax.random.normal(ks[2], (B, Hkv, Sk, d), dt)
    out = flash_attention_fwd(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    err = float(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max())
    assert err < _tol(dt), err


def test_flash_attention_block_shapes_sweep():
    """Block shape must not change results (pure schedule parameter)."""
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    ref = flash_attention_ref(q, k, v, causal=True)
    for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]:
        out = flash_attention_fwd(q, k, v, causal=True, block_q=bq,
                                  block_k=bk, interpret=True)
        assert float(jnp.abs(out - ref).max()) < 3e-5, (bq, bk)


def test_flash_attention_grad_matches_ref_grad():
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 32))
    k = jax.random.normal(ks[1], (1, 2, 64, 32))
    v = jax.random.normal(ks[2], (1, 2, 64, 32))

    def f(fn):
        return jax.grad(lambda q_: fn(q_, k, v).sum())(q)

    g_kernel = f(lambda q_, k_, v_: flash_attention(q_, k_, v_, True, 0))
    g_ref = f(lambda q_, k_, v_: flash_attention_ref(q_, k_, v_, causal=True))
    assert float(jnp.abs(g_kernel - g_ref).max()) < 1e-4


def test_pick_blocks_tile_invariant():
    for sq, sk, d in [(4096, 4096, 128), (100, 300, 64), (32768, 32768, 256)]:
        bq, bk = pick_blocks(sq, sk, d)
        assert sq % bq == 0 and sk % bk == 0


# ---------------------------------------------------------------------------
# rglru scan
# ---------------------------------------------------------------------------

RG_CASES = [(2, 64, 128, 16, 128), (1, 100, 256, 25, 128), (3, 32, 512, 32, 256),
            (1, 128, 128, 128, 128)]


@pytest.mark.parametrize("case", RG_CASES, ids=str)
def test_rglru_vs_ref(case):
    B, S, R, bs, br = case
    a = jax.nn.sigmoid(jax.random.normal(jax.random.key(0), (B, S, R)))
    b = jax.random.normal(jax.random.key(1), (B, S, R))
    out = rglru_scan_pallas(a, b, block_s=bs, block_r=br, interpret=True)
    ref = rglru_scan_ref(a, b)
    assert float(jnp.abs(out - ref).max()) < 1e-5


def test_rglru_grad_matches_ref_grad():
    a = jax.nn.sigmoid(jax.random.normal(jax.random.key(2), (2, 32, 128)))
    b = jax.random.normal(jax.random.key(3), (2, 32, 128))
    g = jax.random.normal(jax.random.key(4), (2, 32, 128))
    da1, db1 = jax.vjp(rglru_scan, a, b)[1](g)
    da2, db2 = jax.vjp(lambda x, y: rglru_scan_ref(x, y), a, b)[1](g)
    assert float(jnp.abs(da1 - da2).max()) < 1e-5
    assert float(jnp.abs(db1 - db2).max()) < 1e-5


# ---------------------------------------------------------------------------
# ssd
# ---------------------------------------------------------------------------

SSD_CASES = [(2, 64, 4, 32, 32, 16, 2), (1, 128, 8, 64, 128, 32, 8),
             (2, 96, 2, 16, 64, 32, 2), (1, 256, 4, 64, 128, 64, 4)]


@pytest.mark.parametrize("case", SSD_CASES, ids=str)
def test_ssd_vs_ref(case):
    b, S, H, P, N, chunk, bh = case
    ks = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(ks[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)))
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (b, S, N))
    C = jax.random.normal(ks[4], (b, S, N))
    out = ssd_pallas(x, dt, A, B, C, chunk=chunk, block_h=bh, interpret=True)
    ref = ssd_ref(x, dt, A, B, C)
    rel = float(jnp.abs(out - ref).max()) / (float(jnp.abs(ref).max()) + 1e-9)
    assert rel < 1e-4, rel


def test_ssd_matches_model_chunked_xla():
    """Kernel and the model's XLA SSD are the same algorithm."""
    from repro.models.ssm import _ssd_chunked
    ks = jax.random.split(jax.random.key(9), 5)
    b, S, H, P, N = 2, 64, 4, 32, 64
    x = jax.random.normal(ks[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)))
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (b, S, N))
    C = jax.random.normal(ks[4], (b, S, N))
    y_kernel = ssd_pallas(x, dt, A, B, C, chunk=16, block_h=2, interpret=True)
    y_xla, _ = _ssd_chunked(x, dt, A, B, C, 16)
    assert float(jnp.abs(y_kernel - y_xla).max()) < 1e-4


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

MM_CASES = [(256, 256, 256, jnp.float32), (128, 384, 256, jnp.bfloat16),
            (64, 64, 64, jnp.float32), (512, 128, 256, jnp.bfloat16)]


@pytest.mark.parametrize("case", MM_CASES, ids=str)
def test_matmul_vs_ref(case):
    M, K, N, dt = case
    a = jax.random.normal(jax.random.key(0), (M, K), dt)
    b = jax.random.normal(jax.random.key(1), (K, N), dt)
    out = matmul(a, b)
    ref = matmul_ref(a, b)
    err = float(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max())
    assert err < (2.0 if dt == jnp.bfloat16 else 1e-3)


def test_matmul_refuses_a_chip_without_a_block_table(monkeypatch):
    """A TPU kind missing from the table is an error, not v5e's blocks."""
    from repro.kernels.matmul import ops
    monkeypatch.setattr(ops, "_platform", lambda: "TPU v99")
    a = jnp.ones((8, 8), jnp.float32)
    with pytest.raises(ValueError, match="no matmul block table for 'TPU v99'"):
        ops.matmul(a, a)
