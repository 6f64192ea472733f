"""Whether the timed path served the right tokens.

Once the window has closed and the program's device state is freed, a
sample of the requests the window served (drawn from the seed, the
longest among them; see ``sample``) is run through the plain reference: each prompt
followed by its served tokens, in float32. At every served position the
gap is the reference's best logit minus the reference's logit of the
token the program served; greedy decoding that follows the model serves
gaps at rounding level. The widest gap is compared with the cell's limit
(``bench/limits/<cell>.json``), and so is the number of tokens checked.
"""

from __future__ import annotations

import numpy as np


def sample(records, t0: float, t1: float, n: int, seed: int) -> list:
    """Up to ``n`` requests served in the window: the one with the longest
    sequence, the one with the most served tokens, then requests finished
    in the window and, where fewer than ``n`` finished, requests still in
    flight at its close with the tokens they had, each group in an order
    drawn from the seed. Long answers outlast a window: in flight, their
    served tokens are checked all the same."""
    done = [r for r in records if r.done_at is not None and not r.failed
            and t0 < r.done_at <= t1]
    live = [r for r in records if r.done_at is None and not r.failed
            and any(t is not None and t0 < t <= t1 for t in r.times)]
    pool = done + live
    if not pool:
        return []
    rng = np.random.default_rng((seed, 2))
    picked = [max(pool, key=lambda r: r.prompt_len + len(r.req.tokens)),
              max(pool, key=lambda r: len(r.req.tokens))]
    picked += [done[i] for i in rng.permutation(len(done))]
    picked += [live[i] for i in rng.permutation(len(live))]
    out = []
    for r in picked:
        if r not in out:
            out.append(r)
    return out[:n]


def sequences(recs) -> tuple[list, list, list]:
    """Each prompt with its served tokens, the positions that predicted
    them, and the served tokens."""
    seqs, wanted, served = [], [], []
    for r in recs:
        prompt = np.asarray(r.req.prompt, np.int32)
        toks = np.asarray(r.req.tokens, np.int32)
        seqs.append(np.concatenate([prompt, toks[:-1]]))
        wanted.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks)))
        served.append(toks)
    return seqs, wanted, served


def gaps(ref_logits, chosen) -> np.ndarray:
    """Reference best logit minus the reference logit of each chosen
    token; a token outside the vocabulary gaps infinitely."""
    out = []
    for lg, tok in zip(ref_logits, chosen):
        best = lg.max(axis=-1)
        ok = (tok >= 0) & (tok < lg.shape[-1])
        got = np.where(ok, lg[np.arange(len(tok)), np.clip(tok, 0,
                                                           lg.shape[-1] - 1)],
                       -np.inf)
        out.append(best - got)
    return np.concatenate(out) if out else np.zeros(0)


def judge(gap: np.ndarray, limits: dict) -> dict:
    """The numbers compared, each with its limit, and the verdict."""
    widest = float(gap.max()) if gap.size else float("inf")
    numbers = {
        "logit_gap_max": {"value": widest,
                          "limit": limits["logit_gap_max"]["limit"],
                          "rule": "<="},
        "tokens_checked": {"value": int(gap.size),
                           "limit": limits["tokens_checked"]["limit"],
                           "rule": ">="},
    }
    ok = (widest <= numbers["logit_gap_max"]["limit"]
          and gap.size >= numbers["tokens_checked"]["limit"])
    return {"correct": bool(ok), "numbers": numbers}


def run(ref, cfg: dict, seed: int, window, limits: dict) -> tuple[dict, dict]:
    """Sample, run the reference, judge. Returns (verdict, details)."""
    recs = sample(window.records, window.t0, window.t1,
                  int(limits["sample_requests"]), seed)
    seqs, wanted, served = sequences(recs)
    if not seqs:
        return judge(np.zeros(0), limits), {"requests": 0}
    ref_logits = ref.logits(cfg, seed, seqs, wanted, "f32")
    gap = gaps(ref_logits, served)
    details = {"requests": len(recs),
               "longest": max(len(s) + 1 for s in seqs),
               "gap_p50": float(np.median(gap)),
               "gap_nonzero": int((gap > 0).sum())}
    return judge(gap, limits), details
