"""The one traffic generator: a mix file of parameters -> seeded requests.

A mix (``bench/traffic/<name>.json``) gives the loop, the load, the length
distributions and the seed of its realisation:

    {"loop": "open", "rate_per_s": 1.3,           # Poisson arrivals, from
     "warm_in_s": 30,                             # 30 s before the window
     "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                "min": 64, "max": 2048},
     "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                "min": 16, "max": 512},
     "shared_prefix": 0, "traffic_seed": 1}

    {"loop": "closed", "clients": 8,             # each client waits for
     "requests": 24, ...}                        # its reply, no think time;
                                                 # 24 distinct requests,
                                                 # taken in turn, cycled

Inter-arrival gaps are independent exponential draws and lengths
independent draws of their distribution, all from the mix's
``traffic_seed``: every run replays the same realisation, the same sizes
at the same times, so two runs offer the same work. The run's ``--seed``
draws the token ids (and the weights), never a size or a time. Open-loop
arrivals start ``warm_in_s`` before the window, so the window opens on the
load in flight that users meet rather than on an empty server.
``shared_prefix`` tokens (one block per run, the same for every request)
lead each prompt and are declared as ``prefix_len``.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Request:
    index: int
    prompt: np.ndarray          # int32 token ids, shared prefix included
    max_new_tokens: int
    prefix_len: int
    due_s: float | None = None  # open loop: seconds after the window opens
    #                             (below 0 during the warm-in)


def _stream(mix: dict, what: int) -> np.random.Generator:
    """The mix's own random stream for gaps (0), prompt lengths (1) or
    output lengths (2)."""
    return np.random.default_rng((int(mix["traffic_seed"]), what))


def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` independent draws of a length distribution, clipped."""
    if dist["dist"] == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * rng.standard_normal(n))
    elif dist["dist"] == "uniform":
        x = rng.integers(dist["min"], dist["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(x), dist["min"], dist["max"]).astype(np.int64)


def shared_prefix(mix: dict, vocab: int, seed: int) -> np.ndarray:
    """The run's shared prefix (empty without one), drawn apart from the
    requests so the warm-up can cache the same block."""
    rng = np.random.default_rng((seed, 1))
    return rng.integers(0, vocab, int(mix.get("shared_prefix", 0)),
                        dtype=np.int32)


def _requests(mix: dict, n: int, vocab: int, seed: int) -> list[Request]:
    shared = int(mix.get("shared_prefix", 0))
    prefix = shared_prefix(mix, vocab, seed)
    p_len = lengths(mix["prompt"], n, _stream(mix, 1))
    o_len = lengths(mix["output"], n, _stream(mix, 2))
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        body = rng.integers(0, vocab, int(p_len[i]), dtype=np.int32)
        out.append(Request(i, np.concatenate([prefix, body]),
                           int(o_len[i]), shared))
    return out


def open_loop(mix: dict, seconds: float, vocab: int,
              seed: int) -> list[Request]:
    """Poisson arrivals at the mix's rate from ``warm_in_s`` before the
    window to its end; ``due_s`` counts from the window's opening. A longer
    window extends the same realisation."""
    warm = float(mix.get("warm_in_s", 0))
    gaps = _stream(mix, 0)
    due, t = [], 0.0
    while True:
        t += gaps.exponential(1.0 / mix["rate_per_s"])
        if t >= warm + seconds:
            break
        due.append(t - warm)
    reqs = _requests(mix, len(due), vocab, seed)
    for r, d in zip(reqs, due):
        r.due_s = d
    return reqs


def closed_loop(mix: dict, vocab: int, seed: int) -> list[Request]:
    """The ``requests`` distinct requests the clients take in turn (and
    again from the start once all are taken): the first ``clients`` fill
    the slots before the window opens."""
    return _requests(mix, int(mix["requests"]), vocab, seed)
