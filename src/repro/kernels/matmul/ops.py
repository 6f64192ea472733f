"""jit'd wrapper with the per-platform block table (run-time binding --
the kernel-analog of the paper's 'compile HPGMG on the host, inside the
container' guidance)."""

from __future__ import annotations

import jax

from repro.kernels.matmul.kernel import matmul_pallas

# TPU device_kind (as JAX reports it) -> (block_m, block_n, block_k); chosen
# for VMEM size & MXU shape. Off the TPU the kernel runs in interpret mode.
BLOCK_TABLE = {
    "TPU v5 lite": (512, 512, 512),     # v5e
    "TPU v4": (512, 1024, 512),
    "cpu-interpret": (128, 128, 128),   # keep interpret-mode tests fast
}


def _platform() -> str:
    if jax.default_backend() != "tpu":
        return "cpu-interpret"
    return jax.devices()[0].device_kind


def matmul(a: jax.Array, b: jax.Array, platform: str | None = None) -> jax.Array:
    platform = platform or _platform()
    if platform not in BLOCK_TABLE:
        # a chip without a tuned entry is an error, not another chip's blocks
        raise ValueError(f"no matmul block table for {platform!r}; "
                         f"known: {sorted(BLOCK_TABLE)}")
    bm, bn, bk = BLOCK_TABLE[platform]
    while a.shape[0] % bm:
        bm //= 2
    while b.shape[1] % bn:
        bn //= 2
    while a.shape[1] % bk:
        bk //= 2
    return matmul_pallas(a, b, block_m=max(bm, 8), block_n=max(bn, 8),
                         block_k=max(bk, 8),
                         interpret=jax.default_backend() != "tpu")
