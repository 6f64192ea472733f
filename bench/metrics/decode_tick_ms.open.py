"""Serve steps, decode: the engine's decode seconds per model tick in the
window (ms), in open-loop cells."""


def read(run):
    n = run.delta["decode_ticks"]
    return 1e3 * run.delta["decode_s"] / n if n else None
