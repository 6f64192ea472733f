"""Shares of the chip's peak: the paged-attention kernel against its
roofline, and the model FLOPs of the useful work."""

from __future__ import annotations

from bench import costs


def is_paged_kernel(op: str, module: str) -> bool:
    """The Pallas paged-attention kernel as the trace shows it: the one
    Pallas call (``tpu_custom_call``) in the paged decode step's program.
    The kernel carries no name of its own into the trace yet."""
    return "decode" in module and op.endswith("tpu_custom_call")


def paged_attention_share(run):
    """Least time for the traced decode steps' attention work over the
    kernel's device time (%). None when the trace holds no kernel event."""
    t = run.trace
    if t is None or not t["kernel_s"]:
        return None
    p = run.peaks
    least = 0.0
    for step in run.trace_steps:
        for tick in range(run.chunk):
            live = [c + tick for c, n in step.decoded if tick < n]
            if not live:
                continue
            flops, nbytes = costs.paged_attention_call(run.dims, live)
            least += run.dims["layers"] * max(
                flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / t["kernel_s"] if least else None


def useful_flops(run, prefill: bool) -> float:
    """Model FLOPs of the window's useful work: every decode token
    delivered in the window at its context and, with ``prefill``, the
    prefill of every request whose first token came in the window."""
    w = run.window
    d = run.dims
    total = 0.0
    for r in w.records:
        for j, t in enumerate(r.times):
            if t is None or not (w.t0 < t <= w.t1):
                continue
            if j == 0:
                if prefill:
                    total += costs.prefill_flops(
                        d, r.prompt_len, run.saved.get(r.req.rid, 0))
            else:
                total += costs.token_flops(d, r.prompt_len + j, True)
    return total
