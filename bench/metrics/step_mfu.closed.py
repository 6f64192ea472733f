"""Model step: model FLOPs of the useful work in the window (prefill of
the uncached prompt positions, and every delivered decode token at its
context) over window seconds x chips x the chip's bf16 peak (%)."""

from bench.roofline import useful_flops


def read(run):
    w = run.window
    flops = useful_flops(run, prefill=True)
    return 100.0 * flops / ((w.t1 - w.t0) * run.chips * run.peaks["bf16_flops"])
