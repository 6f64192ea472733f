"""Page pool / prefix registry: prompt tokens served from cached prefix
pages over all prompt tokens admitted in the window (%)."""


def read(run):
    saved = run.delta["prefix_tokens_saved"]
    total = saved + run.delta["prefill_positions"]
    return 100.0 * saved / total if total else None
