"""Request queue: due time to the start of the step in which the request
left the queue, 95th percentile over requests due in the window; a request
still queued at the window's end counts to the end (s)."""

from bench.stats import due_in_window, nearest_rank


def read(run):
    w = run.window
    waits = [(r.admit_t if r.admit_t is not None else w.t1) - r.due
             for r in due_in_window(w.records, w.t0, w.t1)]
    return nearest_rank(waits, 95)
