"""The seeded weights: the loader hands the program exactly the tensors
the reference regenerates, with the rotary dims reordered."""

import jax
import numpy as np

from bench.loaders import dense_decoder as loader
from bench.reference import dense_decoder as ref
from bench.spec import BENCH, load_json

CFG = load_json(BENCH / "tests" / "data" / "tiny_ln.json")


def abstract():
    from repro.configs import get_config
    from repro.models.params import abstract as abs_
    from repro.models.transformer import Model
    m = Model(get_config("stablelm-3b-smoke"))
    return abs_(m.param_defs(), jax.numpy.float32)


def test_rope_order_pairs_halves():
    assert loader.rope_order(8, 4).tolist() == [0, 2, 1, 3, 4, 5, 6, 7]
    assert loader.rope_order(6, 6).tolist() == [0, 3, 1, 4, 2, 5]


def test_loader_matches_the_reference_layer_by_layer():
    d = ref.dims(CFG)
    seed = 2**32 + 11
    tree = loader.program_params(ref, CFG, seed, abstract(), None)
    order = loader.rope_order(d["hd"], d["rot"])
    base = ref.base_key(seed)
    for layer in range(d["layers"]):
        w = ref.weights_layer(base, layer, d)
        got = jax.tree.map(lambda a: np.asarray(a[layer]), tree["stage0"]["b0"])
        assert np.array_equal(got["attn"]["wq"],
                              np.asarray(w["wq"], np.float32)[..., order])
        assert np.array_equal(got["attn"]["wv"], np.asarray(w["wv"], np.float32))
        assert np.array_equal(got["mlp"]["wd"], np.asarray(w["wd"], np.float32))
        assert np.array_equal(got["ln1"]["bias"],
                              np.asarray(w["ln1"]["bias"], np.float32))
    head = ref.weights_head(base, d)
    assert np.array_equal(np.asarray(tree["embed"]["head"]),
                          np.asarray(head["head"], np.float32))


def test_weights_have_their_spread():
    d = ref.dims(CFG)
    w = ref.weights_layer(ref.base_key(3), 0, d)
    wq = np.asarray(w["wq"], np.float32)
    assert abs(wq.std() * np.sqrt(d["d"]) - 1) < 0.05
    scale = np.asarray(w["ln1"]["scale"], np.float32)
    assert abs(scale.mean() - 1) < 0.05


def test_control_reads_far_above_rounding():
    """The fp8 control over the same sequences puts first tokens whose
    float32 logits lie visibly below the best (the mechanism the cells'
    limits are set against)."""
    from bench import check
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 256, 40).astype(np.int32) for _ in range(3)]
    wanted = [np.arange(10, 40) for _ in seqs]
    f32 = ref.logits(CFG, 7, seqs, wanted, "f32")
    f8 = ref.logits(CFG, 7, seqs, wanted, "fp8")
    self_gap = check.gaps(f32, [lg.argmax(-1) for lg in f32])
    ctrl_gap = check.gaps(f32, [lg.argmax(-1) for lg in f8])
    assert self_gap.max() == 0.0
    assert ctrl_gap.max() > 0.01
    # judged as a run is, with the tiny float32 cell's limits, the
    # control is not correct
    limits = {"logit_gap_max": {"limit": 1e-3}, "tokens_checked": {"limit": 20}}
    assert check.judge(self_gap, limits)["correct"] is True
    assert check.judge(ctrl_gap, limits)["correct"] is False
