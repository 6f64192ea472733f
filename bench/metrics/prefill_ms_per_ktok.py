"""Serve steps, prefill: the engine's prefill seconds per 1000 prompt
positions prefilled in the window (ms)."""


def read(run):
    n = run.delta["prefill_positions"]
    return 1e6 * run.delta["prefill_s"] / n if n else None
