"""Model step, decode: model FLOPs of the decode tokens delivered in the
window over the engine's decode seconds x chips x the bf16 peak (%)."""

from bench.roofline import useful_flops


def read(run):
    s = run.delta["decode_s"]
    if not s:
        return None
    flops = useful_flops(run, prefill=False)
    return 100.0 * flops / (s * run.chips * run.peaks["bf16_flops"])
