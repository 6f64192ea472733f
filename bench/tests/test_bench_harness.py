"""The harness end to end on the CPU at a tiny size: it refuses to run
without a chip, a sound program is judged correct, and a program broken
underneath the timed path is judged not correct."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

from bench import run, spec
from bench.spec import BENCH, ROOT, load_json

DATA = BENCH / "tests" / "data"
# the tiny program computes in float32 like the reference: every served
# token is the reference's best, so any gap is a fault
LIMITS = {"sample_requests": 4, "logit_gap_max": {"limit": 1e-3},
          "tokens_checked": {"limit": 20}}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "deepseek67b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not p.stdout.strip()


def cell(config, mix):
    bm = spec.benchmark()
    return dict(name="tiny", chips=1,
                config=load_json(DATA / f"{config}.json"),
                traffic=load_json(DATA / f"{mix}.json"), limits=LIMITS,
                end_to_end=bm["end_to_end"], per_layer=bm["per_layer"])


def serve(c, tmp_path, monkeypatch, capsys, trace=0, seed=2**31 + 5):
    # a cache of its own: a fault must not be served from a sound build
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    rc = run.execute(c, SimpleNamespace(seed=seed, seconds=1.5, trace=trace),
                     require_chip=False, peaks=PEAKS)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(out)


@pytest.mark.parametrize("config,mix", [("tiny_rms", "tiny_open"),
                                        ("tiny_ln", "tiny_agent")])
def test_sound_program_is_correct(config, mix, tmp_path, monkeypatch,
                                  capsys):
    res = serve(cell(config, mix), tmp_path, monkeypatch, capsys,
                trace=int(mix == "tiny_agent"))
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert res["check"]["logit_gap_max"]["value"] == 0.0
    m = res["metrics"]
    if mix == "tiny_agent":
        assert m["prefix_cached_share"]["value"] > 50
        assert m["window_compiles"]["value"] == 0
        assert 0 < m["slot_occupancy"]["value"] <= 100
    else:
        assert set(m) == {e["name"] for e in spec.benchmark()["end_to_end"]}


def _next_token(logits, vocab_size):
    """Greedy sampling with every token moved one id up."""
    best = jnp.argmax(logits[..., :vocab_size], axis=-1)
    return ((best + 1) % vocab_size).astype(jnp.int32)


def _keep_cache(orig):
    """Paged decode that leaves the KV pool as it found it."""
    def step(p, x, layer_cache, *args, **kw):
        out, _ = orig(p, x, layer_cache, *args, **kw)
        return out, layer_cache
    return step


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(fault, tmp_path, monkeypatch,
                                          capsys):
    from repro.models import attention
    from repro.serve import serve_step
    if fault == "token_altered":
        monkeypatch.setattr(serve_step, "greedy_sample", _next_token)
    else:
        monkeypatch.setattr(attention, "paged_decode_attn",
                            _keep_cache(attention.paged_decode_attn))
    res = serve(cell("tiny_rms", "tiny_open"), tmp_path, monkeypatch, capsys)
    assert res["correct"] is False
    assert res["check"]["logit_gap_max"]["value"] > LIMITS[
        "logit_gap_max"]["limit"]


def test_memory_peak_counts_program_temporaries():
    class Chip:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    # a TPU's reading: buffers in use, the executables' temporaries apart
    full = {"peak_bytes_in_use": 9634708992, "peak_bytes_reserved": 5369200640}
    peak, stats = run.memory_peak([Chip({"peak_bytes_in_use": 10**9}),
                                   Chip(full)])
    assert peak == 9634708992 + 5369200640 and stats is full
    assert run.memory_peak([Chip(None)]) == (0, {})
