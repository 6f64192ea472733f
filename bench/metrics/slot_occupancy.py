"""Pod / scheduler: active slots over slots, sampled at every decode
dispatch in the window (%)."""


def read(run):
    occ = [len(s.decoded) for s in run.window.steps if s.decoded]
    if not occ:
        return None
    return 100.0 * sum(occ) / (len(occ) * run.slots)
