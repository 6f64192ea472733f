"""Paged-attention hardening suite (the PR's foregrounded test work).

Three parity surfaces pinned against each other:
  * Pallas kernel (interpret=True) vs the jnp oracle (ref.py) across page
    sizes {8, 16, 64}, ragged per-slot lengths, GQA/MQA geometry, windowed
    attention and bf16;
  * oracle vs the CONTIGUOUS decode formulation (models.attention.attend
    with per-row positions) -- the exactness that makes paged serving a
    drop-in for slot serving;
plus property/invariant tests for the PagePool allocator under randomized
admit/decode/release schedules (fixed-seed loop, no hypothesis dependency).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import kernel as paged_kernel
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import gather_pages, paged_attention_ref
from repro.orchestrator.page_pool import GARBAGE_PAGE, PagePool

pytestmark = pytest.mark.kernels


def _tol(dt):
    return 3e-2 if dt == jnp.bfloat16 else 3e-5


def _random_paged(rng, B, n_kv, g, hd, ps, mp, lengths, dtype=np.float32):
    """Random pool + a scattered (non-contiguous, shuffled) allocation."""
    n_alloc = sum(-(-int(l) // ps) for l in lengths)
    n_pages = n_alloc + 3                       # garbage page 0 + 2 spare
    free = list(range(1, n_pages))
    rng.shuffle(free)                           # pages land anywhere
    table = np.full((B, mp), GARBAGE_PAGE, np.int32)
    for b in range(B):
        for j in range(-(-int(lengths[b]) // ps)):
            table[b, j] = free.pop()
    q = rng.standard_normal((B, n_kv * g, hd)).astype(dtype)
    k = rng.standard_normal((n_kv, n_pages, ps, hd)).astype(dtype)
    v = rng.standard_normal((n_kv, n_pages, ps, hd)).astype(dtype)
    return (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(table), jnp.asarray(lengths, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------

PA_CASES = [
    # B, n_kv, g, hd, page_size, max_pages, window, dtype
    (4, 2, 2, 16, 8, 4, 0, jnp.float32),
    (3, 1, 8, 32, 16, 3, 0, jnp.float32),      # MQA
    (2, 4, 1, 64, 64, 2, 0, jnp.float32),      # MHA, big pages
    (4, 2, 3, 16, 8, 6, 12, jnp.float32),      # sliding window
    (2, 2, 2, 32, 16, 4, 0, jnp.bfloat16),
    (2, 1, 4, 64, 64, 3, 40, jnp.bfloat16),    # window + big pages
]


@pytest.mark.parametrize("case", PA_CASES, ids=str)
def test_paged_kernel_vs_ref(case):
    B, n_kv, g, hd, ps, mp, window, dt = case
    rng = np.random.default_rng(42)
    # ragged lengths incl. the 1-token edge and a full table span
    lengths = np.concatenate([[1, mp * ps],
                              rng.integers(1, mp * ps, max(0, B - 2)) + 0])
    lengths = lengths[:B].astype(np.int32)
    q, k, v, table, lens = _random_paged(
        rng, B, n_kv, g, hd, ps, mp, lengths,
        np.float32 if dt == jnp.float32 else np.float32)
    if dt == jnp.bfloat16:
        q, k, v = (x.astype(dt) for x in (q, k, v))
    out = paged_attention_pallas(q, k, v, table, lens, window=window,
                                 interpret=True)
    ref = paged_attention_ref(q, k, v, table, lens, window=window)
    err = float(jnp.abs(out.astype(jnp.float32)
                        - ref.astype(jnp.float32)).max())
    assert err < _tol(dt), err


# the served schedule walks several blocks of ``ppb`` table entries per
# slot; these cases pin ppb small (monkeypatched) so that every slot
# crosses blocks, its prefetch runs into the same slot's next block and
# into the next slot's first, and the table's width is no multiple of it.
# A slot of length 1 is a parked one: free slots decode at position 0
# through an all-garbage table row.
BLOCK_CASES = [
    # B, n_kv, g, hd, page_size, max_pages, ppb, window, dtype, lengths
    (3, 2, 2, 16, 16, 7, 2, 0, jnp.float32, [1, 112, 57]),
    (4, 2, 2, 16, 16, 13, 4, 0, jnp.float32, [208, 3, 64, 65]),
    (4, 1, 4, 32, 16, 6, 2, 0, jnp.float32, [32, 33, 64, 65]),
    (2, 32, 1, 80, 16, 5, 2, 0, jnp.bfloat16, [80, 33]),
    (2, 8, 8, 128, 16, 6, 4, 0, jnp.bfloat16, [96, 65]),
    (3, 2, 2, 16, 16, 13, 2, 100, jnp.float32, [200, 208, 70]),
    (2, 2, 3, 16, 8, 12, 2, 40, jnp.bfloat16, [96, 41]),
    # parked slots beside long ones, at the cells' head geometries
    (4, 2, 2, 16, 16, 7, 2, 0, jnp.float32, [1, 100, 1, 112]),
    (4, 32, 1, 80, 16, 7, 2, 0, jnp.bfloat16, [1, 100, 1, 112]),
    (4, 8, 8, 128, 16, 7, 2, 0, jnp.bfloat16, [1, 100, 1, 112]),
]


@pytest.mark.parametrize("case", BLOCK_CASES, ids=str)
def test_paged_kernel_blocks_vs_ref(case, monkeypatch):
    B, n_kv, g, hd, ps, mp, ppb, window, dt, lengths = case
    monkeypatch.setattr(paged_kernel, "pages_per_block", lambda *a: ppb)
    rng = np.random.default_rng(7)
    q, k, v, table, lens = _random_paged(
        rng, B, n_kv, g, hd, ps, mp, np.asarray(lengths, np.int32))
    table = jnp.where(lens[:, None] == 1, GARBAGE_PAGE, table)
    q, k, v = (x.astype(dt) for x in (q, k, v))
    out = paged_attention_pallas(q, k, v, table, lens, window=window,
                                 interpret=True)
    ref = paged_attention_ref(q, k, v, table, lens, window=window)
    err = float(jnp.abs(out.astype(jnp.float32)
                        - ref.astype(jnp.float32)).max())
    assert err < _tol(dt), err


@pytest.mark.parametrize("n_kv,hd,mp,want", [
    (8, 128, 161, 32),     # deepseek-67b stage: 64 KiB of K+V a page
    (32, 80, 193, 8),      # stablelm-3b: hd 80 lies in 128 lanes
    (8, 128, 6, 4),        # no wider than the table
    (64, 1024, 100, 1),    # a page over the budget still makes a block
], ids=["deepseek", "stablelm", "narrow-table", "huge-page"])
def test_pages_per_block_from_shapes(n_kv, hd, mp, want):
    ppb = paged_kernel.pages_per_block(n_kv, 16, hd, 2, mp)
    assert ppb == want
    assert ppb & (ppb - 1) == 0


@pytest.mark.parametrize("page_size", [8, 16, 64])
def test_page_size_is_pure_layout(page_size):
    """The same logical KV history must attend identically regardless of
    how it is cut into pages (page size is a layout parameter, like the
    flash kernel's block shapes)."""
    rng = np.random.default_rng(0)
    B, n_kv, g, hd, L = 3, 2, 2, 32, 128
    lengths = np.array([1, 70, 128], np.int32)
    kc = rng.standard_normal((B, L, n_kv, hd)).astype(np.float32)
    vc = rng.standard_normal((B, L, n_kv, hd)).astype(np.float32)
    q = rng.standard_normal((B, n_kv * g, hd)).astype(np.float32)

    # page the contiguous history through a shuffled allocation
    mp = L // page_size
    n_pages = B * mp + 1
    perm = list(range(1, n_pages))
    rng.shuffle(perm)
    table = np.zeros((B, mp), np.int32)
    k_pages = np.zeros((n_kv, n_pages, page_size, hd), np.float32)
    v_pages = np.zeros((n_kv, n_pages, page_size, hd), np.float32)
    for b in range(B):
        for j in range(mp):
            p = perm.pop()
            table[b, j] = p
            sl = slice(j * page_size, (j + 1) * page_size)
            k_pages[:, p] = kc[b, sl].transpose(1, 0, 2)
            v_pages[:, p] = vc[b, sl].transpose(1, 0, 2)

    # contiguous decode formulation (what models.attention.decode_attn runs)
    from repro.models.attention import attend
    q_pos = (lengths - 1)[:, None]
    k_pos = np.broadcast_to(np.arange(L), (B, L))
    ref_c = attend(jnp.asarray(q)[:, None], jnp.asarray(kc), jnp.asarray(vc),
                   jnp.asarray(q_pos), jnp.asarray(k_pos))[:, 0]

    args = (jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(table), jnp.asarray(lengths))
    ref_p = paged_attention_ref(*args)
    out_k = paged_attention_pallas(*args, interpret=True)
    # oracle == contiguous path bitwise (same einsum/mask formulation);
    # kernel within online-softmax tolerance
    np.testing.assert_array_equal(np.asarray(ref_p), np.asarray(ref_c))
    assert float(jnp.abs(out_k - ref_c).max()) < 3e-5


def test_unmapped_pages_and_garbage_are_invisible():
    """Poisoning the garbage page and every unallocated page must not
    change any output: the mask, not the allocator, hides junk."""
    rng = np.random.default_rng(1)
    B, n_kv, g, hd, ps, mp = 3, 2, 2, 16, 8, 5
    lengths = np.array([3, 17, 26], np.int32)
    q, k, v, table, lens = _random_paged(rng, B, n_kv, g, hd, ps, mp, lengths)
    base = paged_attention_ref(q, k, v, table, lens)
    used = np.unique(np.asarray(table))
    poison = np.ones(k.shape, np.float32) * 1e9
    mask = np.zeros(k.shape, bool)
    mask[:, used] = True                 # keep used pages, poison the rest
    kp = jnp.where(jnp.asarray(mask), k, jnp.asarray(poison))
    vp = jnp.where(jnp.asarray(mask), v, jnp.asarray(poison))
    np.testing.assert_array_equal(
        np.asarray(base), np.asarray(paged_attention_ref(q, kp, vp, table, lens)))
    out_k = paged_attention_pallas(q, kp, vp, table, lens, interpret=True)
    assert float(jnp.abs(out_k - base).max()) < 3e-5


def test_ops_dispatch_off_tpu_uses_oracle():
    rng = np.random.default_rng(2)
    lengths = np.array([5, 9], np.int32)
    q, k, v, table, lens = _random_paged(rng, 2, 2, 2, 16, 8, 2, lengths)
    np.testing.assert_array_equal(
        np.asarray(paged_attention(q, k, v, table, lens)),
        np.asarray(paged_attention_ref(q, k, v, table, lens)))


def test_gather_pages_roundtrip():
    rng = np.random.default_rng(3)
    lengths = np.array([16, 16], np.int32)
    _, k, _, table, _ = _random_paged(rng, 2, 2, 1, 16, 8, 2, lengths)
    got = gather_pages(k, table)
    assert got.shape == (2, 16, 2, 16)
    for b in range(2):
        for j in range(2):
            np.testing.assert_array_equal(
                np.asarray(got[b, j * 8:(j + 1) * 8]),
                np.asarray(k[:, int(table[b, j])]).transpose(1, 0, 2))


# ---------------------------------------------------------------------------
# PagePool properties (randomized schedule, fixed seed, no hypothesis dep)
# ---------------------------------------------------------------------------

def test_page_pool_random_schedules_conserve_pages():
    """500 random admit/extend/release steps: pages are never leaked, never
    double-allocated, reservations never over-commit, and the free count is
    conserved -- ``check()`` asserts the full invariant set after EVERY op."""
    rng = np.random.default_rng(0)
    pool = PagePool(n_pages=33, page_size=8, n_slots=6, max_pages=12)
    hi = {}                                    # slot -> high-water position
    goal = {}                                  # slot -> reserved page count
    for _ in range(500):
        op = rng.integers(0, 3)
        busy = list(hi)
        free_slots = [s for s in range(6) if s not in hi]
        if op == 0 and free_slots:             # admit
            slot = int(rng.choice(free_slots))
            need = int(rng.integers(1, 9))
            if pool.can_reserve(need):
                pool.reserve(slot, need)
                goal[slot] = need
                hi[slot] = int(rng.integers(0, need * 8))
                pool.alloc_upto(slot, hi[slot])
        elif op == 1 and busy:                 # decode: extend alloc-on-write
            slot = int(rng.choice(busy))
            hi[slot] = min(goal[slot] * 8 - 1,
                           hi[slot] + int(rng.integers(1, 5)))
            pool.alloc_upto(slot, hi[slot])
        elif op == 2 and busy:                 # release
            slot = int(rng.choice(busy))
            pool.release(slot)
            del hi[slot], goal[slot]
        pool.check()
    for slot in list(hi):
        pool.release(slot)
    pool.check()
    assert pool.in_use == 0 and pool.total_reserved == 0
    assert len(pool.free) == pool.capacity
    assert pool.pages_allocated == pool.pages_freed > 0


def test_page_pool_rejects_overcommit_and_double_reserve():
    pool = PagePool(n_pages=9, page_size=4, n_slots=2, max_pages=4)
    assert pool.capacity == 8
    pool.reserve(0, 6)
    assert not pool.can_reserve(3)             # only 2 unreserved left
    with pytest.raises(RuntimeError):
        pool.reserve(1, 3)
    with pytest.raises(RuntimeError):
        pool.reserve(0, 1)                     # slot already reserved
    pool.alloc_upto(0, 7)                      # 2 pages, within reservation
    with pytest.raises(RuntimeError):
        pool.alloc_upto(0, 6 * 4)              # would exceed the reservation
    pool.release(0)
    assert pool.can_reserve(8)
    pool.check()


def test_page_pool_early_release_returns_unused_reservation():
    """EOS-style exit: a request that reserved 6 pages but only wrote 2
    gives all 6 back the moment it releases."""
    pool = PagePool(n_pages=13, page_size=4, n_slots=2, max_pages=8)
    pool.reserve(0, 6)
    pool.alloc_upto(0, 7)                      # wrote 2 pages of 6
    assert pool.in_use == 2 and pool.free_unreserved == pool.capacity - 6
    pool.release(0)
    assert pool.in_use == 0 and pool.free_unreserved == pool.capacity
    pool.check()


def test_page_pool_garbage_page_is_never_allocated():
    pool = PagePool(n_pages=5, page_size=4, n_slots=1, max_pages=4)
    pool.reserve(0, 4)
    pool.alloc_upto(0, 15)                     # exhaust the whole pool
    assert GARBAGE_PAGE not in pool.owned[0]
    assert (pool.table[0] != GARBAGE_PAGE).all()
    pool.release(0)
    assert (pool.table[0] == GARBAGE_PAGE).all()
    pool.check()
