"""Serve steps: median host time from one decode chunk's read-back to
the next decode dispatch, over consecutive dispatches in the traced window
with no prefill between them, 0 where the next dispatch came first (ms),
in open-loop cells."""

from bench import program_trace


def read(run):
    t = program_trace.traced(run)
    return None if t is None else program_trace.decode_turnaround_ms(t)
