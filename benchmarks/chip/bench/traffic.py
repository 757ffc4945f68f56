"""The one general traffic generator: every mix is a JSON file of
parameters under ``traffic/`` that this module reads.

Keys of a mix:
  ``mode``        decoding mode of every request (the engine's slot group)
  ``load``        ``"backlog"``: a closed loop that keeps ``backlog_slots``
                  x n_slots requests queued beyond the slots, so no slot
                  waits; ``"poisson"``: an open loop at ``rate_per_s``
  ``rate_per_s``  open-loop arrival rate (requests per second)
  ``drain_s``     after the window, how long due requests may still finish
  ``pool``        ``{"size": n, "seed": s}``: the query pool

Every seed serves the same pool of queries and, in an open loop, the same
multiset of inter-arrival gaps (quantiles of the exponential
distribution); ``--seed`` only orders them. A backlog cycles through the
pool in the seed's order, so a pool several times smaller than what one
window serves holds the work of a run nearly fixed: seeds change which
request comes when, not how much work a run holds.
"""

from __future__ import annotations

import numpy as np

from bench import chem

LOADS = ("backlog", "poisson")


def validate(mix: dict) -> dict:
    if mix.get("load") not in LOADS:
        raise ValueError(f"traffic load {mix.get('load')!r} not in {LOADS}")
    if mix["load"] == "poisson" and not mix.get("rate_per_s", 0) > 0:
        raise ValueError("a poisson mix needs rate_per_s > 0")
    return mix


def pool(direction: str, mix: dict, max_src: int, max_new: int):
    """The mix's query pool: ``src`` (P, max_src) EOS-terminated ids, and
    the reference targets ``tgt`` (P, max_new)."""
    p = mix["pool"]
    prs = chem.pairs(int(p["size"]), int(p["seed"]), direction)
    return chem.encode_pairs(chem.Tokenizer(), prs, max_src, max_new)


def order(n: int, seed: int) -> np.ndarray:
    """The seed's permutation of ``n`` pool entries."""
    return np.random.default_rng(seed).permutation(n)


def due_times(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Open-loop due times (seconds from the window's start) of the
    ``floor(rate * seconds)`` requests of one window. The gaps are the
    exponential distribution's quantiles at (i + 1/2) / n, in the seed's
    order, so their sum, and the window's load, is the same for every
    seed."""
    n = int(rate * seconds)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps = gaps[np.random.default_rng(seed).permutation(n)]
    due = np.cumsum(gaps) - gaps[0]
    return due[due < seconds]
