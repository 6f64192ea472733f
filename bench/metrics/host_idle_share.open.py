"""Pod and scheduler: share of the traced window in which no op ran on
the device while the host was inside a program span (``repro.*``) other
than a ``*.wait``, the innermost span deciding (%), in open-loop cells."""

from bench import program_trace


def read(run):
    t = program_trace.traced(run)
    return None if t is None else program_trace.host_idle_share(t)
