"""Operation and byte counts at both configurations' widths, by hand."""

import pytest

from bench import costs
from bench.reference import dense_decoder as ref
from bench.spec import BENCH, load_json


def dims(name):
    return ref.dims(load_json(BENCH / "configs" / f"{name}.json"))


DS = "deepseek-67b.stage6"
SL = "stablelm-3b-4e1t"


def test_dims_from_published_keys():
    d = dims(DS)
    assert (d["d"], d["h"], d["kv"], d["hd"], d["f"], d["layers"],
            d["vocab"], d["layernorm"], d["rot"]) == (
        8192, 64, 8, 128, 22016, 6, 102400, False, 128)
    s = dims(SL)
    assert (s["d"], s["h"], s["kv"], s["hd"], s["f"], s["layers"],
            s["vocab"], s["layernorm"], s["rot"], s["eps"]) == (
        2560, 32, 32, 80, 6912, 32, 50304, True, 20, 1e-5)


@pytest.mark.parametrize("name,per_layer,total", [
    # q 8192x8192, k and v 8192x1024 each, o 8192x8192, gate/up/down
    # 3 x 8192x22016; plus embedding and head 2 x 102400x8192
    (DS, 67108864 + 2 * 8388608 + 67108864 + 3 * 180355072,
     6 * 692060160 + 2 * 838860800),
    # MHA: q, k, v, o 2560x2560 each; 3 x 2560x6912; 2 x 50304x2560
    (SL, 4 * 6553600 + 3 * 17694720, 32 * 79298560 + 2 * 128778240),
])
def test_matmul_params(name, per_layer, total):
    d = dims(name)
    assert costs.layer_matmul_params(d) == per_layer
    assert (d["layers"] * costs.layer_matmul_params(d)
            + 2 * d["vocab"] * d["d"]) == total


def test_param_totals_match_the_published_sizes():
    # 4.152 B in 6 layers + 1.678 B in the vocabulary ends; 2.795 B
    assert 6 * 692060160 + 2 * 838860800 == 5830082560
    assert 32 * 79298560 + 2 * 128778240 == 2795110400


def test_paged_attention_call_deepseek():
    d = dims(DS)
    flops, nbytes = costs.paged_attention_call(d, [100, 300])
    # 4 * 64 heads * 128 * context
    assert flops == 4 * 64 * 128 * 400
    # K and V: 2 * 8 kv heads * 128 * 400 live tokens * 2 bytes,
    # plus q and out: 2 * 64 * 128 * 2 slots * 2 bytes
    assert nbytes == 2 * 8 * 128 * 400 * 2 + 2 * 64 * 128 * 2 * 2


def test_paged_attention_call_stablelm():
    d = dims(SL)
    flops, nbytes = costs.paged_attention_call(d, [2048])
    assert flops == 4 * 32 * 80 * 2048 == 20971520
    assert nbytes == 2 * 32 * 80 * 2048 * 2 + 2 * 32 * 80 * 2 == 20981760


def test_token_and_prefill_flops():
    d = dims(DS)
    per_layer = 2 * 692060160
    head = 2 * 8192 * 102400
    assert costs.token_flops(d, 1000, True) == (
        6 * (per_layer + 4 * 64 * 128 * 1000) + head)
    assert costs.token_flops(d, 1000, False) == 6 * (
        per_layer + 4 * 64 * 128 * 1000)
    # 3 positions over contexts 1, 2, 3 (no cache) and one head
    assert costs.prefill_flops(d, 3) == (
        3 * 6 * per_layer + 6 * 4 * 64 * 128 * 6 + head)
    # 2 uncached positions over contexts 4 and 5 behind a cached 3
    assert costs.prefill_flops(d, 5, cached=3) == (
        2 * 6 * per_layer + 6 * 4 * 64 * 128 * 9 + head)

