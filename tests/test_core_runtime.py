"""Runtime facade + CompileCache (the import-problem fix) + Container overlay
+ data pipeline determinism."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.core.compile_cache import (CHECKOUT, CompileCache, _atomic_bytes,
                                      resolve_cache_dir)
from repro.core.runtime import Runtime
from repro.data.pipeline import DataConfig, MemmapLM, SyntheticLM

SMOKE_IMAGEFILE = """
FROM scratch
ARCH llama3.2-3b-smoke
SHAPE train_4k seq_len=16 global_batch=2
MESH local
PRECISION compute=float32 params=float32
COLLECTIVES generic
"""


@pytest.fixture()
def rt(tmp_path, monkeypatch):
    # a compile cache of this test's own: the hit/miss counts below start
    # from an empty cache (the shared one lives in the checkout), and JAX's
    # persistent cache is off so the cold compile really compiles
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield Runtime(tmp_path / "stevedore")
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def test_runtime_build_run_train(rt):
    img = rt.build(SMOKE_IMAGEFILE, tag="smoke")
    c = rt.run("smoke")
    prm = c.init_params(0)
    opt = c.init_opt_state(prm)
    step = jax.jit(c.train_step_fn())
    batch = {
        "tokens": jnp.zeros((2, 16), jnp.int32),
        "labels": jnp.zeros((2, 16), jnp.int32),
    }
    _, _, metrics = step(prm, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    # overlay exists and records the image
    meta = json.loads((c.overlay / "container.json").read_text())
    assert meta["image"] == img.digest
    assert rt.ps()[0]["arch"] == "llama3.2-3b-smoke"


def test_containers_share_image_but_not_overlay(rt):
    rt.build(SMOKE_IMAGEFILE, tag="smoke")
    c1, c2 = rt.run("smoke"), rt.run("smoke")
    assert c1.image.digest == c2.image.digest
    assert c1.overlay != c2.overlay


def test_container_metrics_log(rt):
    rt.build(SMOKE_IMAGEFILE, tag="smoke")
    c = rt.run("smoke")
    c.log_metrics(1, {"loss": jnp.float32(2.5)})
    c.log_metrics(2, {"loss": 2.4})
    lines = (c.overlay / "metrics.jsonl").read_text().splitlines()
    assert json.loads(lines[0]) == {"step": 1, "loss": 2.5}


# ---------------------------------------------------------------------------
# compile cache = the Python-import-problem fix (paper Fig. 4)
# ---------------------------------------------------------------------------

def test_compile_cache_levels(rt):
    rt.build(SMOKE_IMAGEFILE, tag="smoke")
    c = rt.run("smoke")
    compiled_cold = c.compile_step("train")
    assert rt.compile_cache.stats.misses == 1
    assert rt.compile_cache.stats.last_level == "L0"
    cold_s = rt.compile_cache.stats.last_seconds

    c2 = rt.run("smoke")                      # second "host"
    compiled_warm = c2.compile_step("train")
    assert rt.compile_cache.stats.hits_l1 == 1
    assert rt.compile_cache.stats.last_level == "L1"
    assert rt.compile_cache.stats.last_seconds < cold_s

    # the deserialized executable actually runs and matches (params/opt are
    # donated by the train step, so each call gets a fresh copy)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    _, _, m1 = compiled_cold(c.init_params(0), c.init_opt_state(
        c.init_params(0)), batch)
    _, _, m2 = compiled_warm(c2.init_params(0), c2.init_opt_state(
        c2.init_params(0)), batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), abs=1e-6)


def test_compile_cache_key_separates_configs(tmp_path):
    cache = CompileCache(tmp_path)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    args = {"x": jax.ShapeDtypeStruct((4,), jnp.float32)}
    k1 = cache.key(image_digest="a" * 64, step_kind="train", mesh=mesh,
                   args_tree=args)
    k2 = cache.key(image_digest="b" * 64, step_kind="train", mesh=mesh,
                   args_tree=args)
    k3 = cache.key(image_digest="a" * 64, step_kind="decode", mesh=mesh,
                   args_tree=args)
    assert len({k1, k2, k3}) == 3


def test_compile_cache_dir_is_resolved_not_derived_from_root(
        tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    a, b = Runtime(tmp_path / "a"), Runtime(tmp_path / "b")
    assert a.compile_cache.root == b.compile_cache.root == tmp_path / "cc"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = CHECKOUT / ".stevedore" / "compile-cache"
    assert resolve_cache_dir() == fixed
    assert Runtime(tmp_path / "c").compile_cache.root == fixed
    # JAX's own persistent cache is pointed into the checkout too, unless
    # something already placed it
    assert jax.config.jax_compilation_cache_dir


def test_compile_cache_key_names_the_chip(tmp_path):
    cache = CompileCache(tmp_path)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    key = cache.key(image_digest="a" * 64, step_kind="t", mesh=mesh,
                    args_tree=None)
    from unittest import mock
    with mock.patch.object(jax.devices()[0].__class__, "device_kind",
                           "TPU v5 lite", create=True):
        other = cache.key(image_digest="a" * 64, step_kind="t", mesh=mesh,
                          args_tree=None)
    assert key != other


def test_atomic_writes_never_interleave(tmp_path):
    """Many writers on one path, more threads than cores: every read sees
    one writer's whole payload, and no temporary file is left behind."""
    import threading
    target = tmp_path / "k.exec"
    payloads = [bytes([i]) * (1 << 16) for i in range(4 * (os.cpu_count() or 1))]
    seen, errors = [], []

    def write(data):
        try:
            for _ in range(5):
                _atomic_bytes(target, data)
                seen.append(target.read_bytes())
        except Exception as e:   # reported below, not lost in the thread
            errors.append(e)

    threads = [threading.Thread(target=write, args=(p,)) for p in payloads]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert not errors
    assert all(s in payloads for s in seen)
    assert [p.name for p in tmp_path.iterdir()] == ["k.exec"]


def test_compile_cache_lowered_text_persisted(tmp_path):
    cache = CompileCache(tmp_path)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    x = jax.ShapeDtypeStruct((8,), jnp.float32)
    key = cache.key(image_digest="c" * 64, step_kind="t", mesh=mesh,
                    args_tree=x)
    cache.get_or_build(key, lambda: jax.jit(lambda v: v * 2).lower(x))
    text = cache.lowered_text(key)
    assert text and "stablehlo" in text or "module" in text


SUBMESH_L1 = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.compile_cache import CompileCache
cache = CompileCache(sys.argv[1])
mesh = jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[2:3])
rep = NamedSharding(mesh, P())
x = jax.device_put(jnp.arange(8.0), rep)
key = cache.key(image_digest="d" * 64, step_kind="t", mesh=mesh, args_tree=x)
lower = lambda: jax.jit(lambda v: v * 2, in_shardings=rep,
                        out_shardings=rep).lower(x)
cold = cache.get_or_build(key, lower)
warm = cache.get_or_build(key, lower)
assert cache.stats.last_level == "L1", cache.stats
assert (warm(x) == cold(x)).all() and warm(x).devices() == {jax.devices()[2]}
print("SUBMESH_L1_OK")
"""


def test_compile_cache_l1_loads_onto_the_compiled_devices(tmp_path):
    """An executable built for one chip of four comes back from L1 on that
    chip, not spread over the host's every device."""
    r = subprocess.run([sys.executable, "-c", SUBMESH_L1, str(tmp_path)],
                       capture_output=True, text=True,
                       env={**os.environ, "PYTHONPATH": "src"}, cwd=".")
    assert "SUBMESH_L1_OK" in r.stdout, r.stdout + r.stderr[-2000:]


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_synthetic_deterministic_and_restartable():
    cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=4, seed=1)
    d1, d2 = SyntheticLM(cfg), SyntheticLM(cfg)
    b5 = d1.batch(5)
    np.testing.assert_array_equal(b5["tokens"], d2.batch(5)["tokens"])
    assert b5["tokens"].shape == (4, 8)
    assert b5["tokens"].max() < 100
    # labels are next-token shifted
    assert not np.array_equal(b5["tokens"], b5["labels"])


def test_synthetic_differs_across_steps_and_seeds():
    cfg = DataConfig(vocab_size=1000, seq_len=32, global_batch=2, seed=1)
    d = SyntheticLM(cfg)
    assert not np.array_equal(d.batch(0)["tokens"], d.batch(1)["tokens"])
    d2 = SyntheticLM(DataConfig(vocab_size=1000, seq_len=32, global_batch=2,
                                seed=2))
    assert not np.array_equal(d.batch(0)["tokens"], d2.batch(0)["tokens"])


def test_memmap_source_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 50, size=20_000).astype(np.int32)
    MemmapLM.write_shards(tmp_path, tokens, n_shards=3)
    cfg = DataConfig(vocab_size=50, seq_len=16, global_batch=4)
    src = MemmapLM(cfg, tmp_path)
    b = src.batch(0)
    assert b["tokens"].shape == (4, 16)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
    np.testing.assert_array_equal(src.batch(3)["tokens"],
                                  src.batch(3)["tokens"])
