"""Request mixes: lognormal prompt and answer lengths, optional shared system
prompts, arriving open-loop or closed-loop.

Parameters (a traffic file's ``params``):

* ``prompt`` / ``answer``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal length; prompt plus answer never exceed the engine's max_len;
* ``shared_share``, ``n_shared``, ``shared_len``: the share of requests
  that open with one of ``n_shared`` system prompts of ``shared_len``
  tokens, and ``min_user`` tokens that follow it at least;
* ``block``: requests are drawn in blocks of this many. Each block holds
  the same multiset of gaps and lengths (the distribution's quantiles at
  ``(j + 1/2) / block``), the shared flags sitting at evenly spread prompt
  lengths; the seed permutes them within the block and draws every token
  id. So every seed offers the same work, in another order, and a window
  of a few blocks sees the whole mix.

Arrivals (a cell's ``arrivals``):

* ``{"kind": "poisson", "rate_per_s": r}``: open loop, each request due at
  a fixed time, exponential gaps at rate ``r``. The gaps of a block are
  scaled to span exactly ``block / r`` seconds, blocks follow each other
  from ``origin_s`` on (and before it, down to 0), and each request is due
  in the middle of its gap. So a window that opens at ``origin_s`` and
  lasts a whole number of blocks holds the same requests for every seed,
  none of them on its edges;
* ``{"kind": "closed", "clients": c, "rounds": n}``: ``c`` clients, each
  sending its next request as soon as its last one has finished; request
  ``j`` belongs to client ``j % c``. The first block (make ``block`` the
  number of clients) stands for requests already under way when the run
  starts: its answers are cut to the shares ``(j + 1/2) / block`` of their
  lengths (the longest answer keeping the largest share), so the clients'
  requests end at spread times instead of together.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    due: float               # seconds after the start (open loop)
    prompt: np.ndarray       # int32 token ids
    max_new: int
    shared: int = -1         # system prompt index, -1 = none
    client: int = -1         # closed loop: the client that sends it


def _quantiles(block: int) -> np.ndarray:
    return (np.arange(block) + 0.5) / block


def lognormal_lengths(spec: Dict, block: int) -> np.ndarray:
    """Ascending: the lognormal's quantiles at ``(j + 1/2) / block``."""
    z = np.array([statistics.NormalDist().inv_cdf(q)
                  for q in _quantiles(block)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate: float, block: int) -> np.ndarray:
    """The exponential's quantiles, scaled to sum to ``block / rate``."""
    g = -np.log1p(-_quantiles(block))
    return g * (block / rate / g.sum())


def generate(params: Dict, *, arrivals: Dict, rng: np.random.Generator,
             horizon_s: float, vocab: int, max_len: int,
             origin_s: float = 0.0) -> List[Req]:
    """Open loop: the requests due in ``[0, horizon_s)``, a block starting
    at ``origin_s``. Closed loop: ``rounds`` requests per client, all due at
    0 and released one after another by the client loop
    (``harness/driver.py``)."""
    block = int(params.get("block", 64))
    prompts = lognormal_lengths(params["prompt"], block)
    answers = lognormal_lengths(params["answer"], block)
    closed = arrivals["kind"] == "closed"
    if closed:
        clients = int(arrivals["clients"])
        total = clients * int(arrivals["rounds"])
        gaps = np.zeros(block)
        first_round = np.maximum(
            1, np.round(answers * _quantiles(block))).astype(np.int64)
    elif arrivals["kind"] == "poisson":
        gaps = exponential_gaps(float(arrivals["rate_per_s"]), block)
        span = float(gaps.sum())
        start = origin_s - math.ceil(origin_s / span) * span
    else:
        raise ValueError(f"unknown arrivals {arrivals['kind']!r}")
    share = float(params.get("shared_share", 0.0))
    n_sys = int(params.get("n_shared", 0))
    sys_len = int(params.get("shared_len", 0))
    min_user = int(params.get("min_user", 1))
    # shared flags sit at evenly spread prompt-length quantiles and move
    # with their length, so the multiset of prompts is the seed's too
    j = np.arange(block)
    flags = np.floor((j + 1) * share) > np.floor(j * share)
    systems = [rng.integers(0, vocab, sys_len).astype(np.int32)
               for _ in range(n_sys)]
    reqs: List[Req] = []
    n_shared_seen = 0
    while True:
        perm = rng.permutation(block)
        ans = first_round if closed and not reqs else answers
        g, p, a, f = (rng.permutation(gaps), prompts[perm],
                      rng.permutation(ans), flags[perm])
        due = 0.0 if closed else start + np.cumsum(g) - g / 2
        if not closed:
            start += span
        for j in range(block):
            t = 0.0 if closed else float(due[j])
            if (len(reqs) >= total) if closed else (t >= horizon_s):
                return reqs
            plen = int(p[j])
            sys_idx = -1
            if t < 0:                   # before the schedule's start
                continue
            if f[j] and n_sys:
                sys_idx = n_shared_seen % n_sys
                n_shared_seen += 1
                plen = max(plen, sys_len + min_user)
            plen = min(plen, max_len - 1)
            new = int(min(a[j], max_len - plen))
            prompt = rng.integers(0, vocab, plen).astype(np.int32)
            if sys_idx >= 0:
                prompt[:sys_len] = systems[sys_idx]
            rid = len(reqs)
            reqs.append(Req(rid, t, prompt, new, sys_idx,
                            rid % clients if closed else -1))
