"""Kernels: the Pallas paged-attention kernel's device time in the traced
window against the least time the chip needs for its work, the live
context of the active slots (%), in closed-loop cells."""

from bench.roofline import paged_attention_share


def read(run):
    return paged_attention_share(run)
