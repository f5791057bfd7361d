"""How ``correct`` is decided for a served model.

After the window, a sample of finished requests drawn from the seed, the
longest of them always in it, is run through the plain reference once per
request: the prompt and the tokens the engine served, teacher-forced. For
each served token the number read is how far its reference logit lies
below the reference's best at that position (0 where the engine chose the
reference's greedy token). Compared with the cell's limits (each one that
its ``check`` names): the widest such gap over the sample
(``max_logit_gap``), the share of served tokens that are the reference's
first (``top_share``, against ``min_top_share``), and the served tokens in
the sample (``tokens_compared``, against ``min_tokens``). Each limit and
the readings it was set from are in PERF.md.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import numpy as np


def sample(finished: Sequence, rng: np.random.Generator, min_tokens: int,
           max_requests: int) -> List:
    """Finished items (each with ``prompt`` and ``tokens``): the longest, then
    others in seeded order until ``min_tokens`` served tokens are held."""
    items = [f for f in finished if len(f.tokens) > 0]
    if not items:
        return []
    longest = max(range(len(items)),
                  key=lambda i: (len(items[i].prompt) + len(items[i].tokens),
                                 -i))
    chosen = [items[longest]]
    n = len(items[longest].tokens)
    for i in rng.permutation(len(items)):
        if n >= min_tokens or len(chosen) >= max_requests:
            break
        if i == longest:
            continue
        chosen.append(items[i])
        n += len(items[i].tokens)
    return chosen


def compare(ref, items: Sequence, *, seq_len: int, n_read: int,
            control: Optional[dict] = None) -> Dict[str, float]:
    """Widest served-token gap (and the control's, if given) over ``items``.
    ``ref`` is (quantized weights, shape, arithmetic) of the reference."""
    from bench.reference import dense_lm
    qw, shape, arith = ref
    served, ctrl = [], []
    t0 = time.perf_counter()
    for it in items:
        g = dense_lm.gaps(qw, shape, arith, it.prompt, it.tokens,
                          seq_len=seq_len, n_read=n_read, control=control)
        served.append(g["served"])
        if control is not None:
            ctrl.append(g["control"])
    served = np.concatenate(served) if served else np.zeros(0)
    out = {"max_logit_gap": float(served.max(initial=0.0)),
           "top_share": float((served == 0).mean()) if served.size else 0.0,
           "tokens_compared": int(served.size),
           "requests_compared": len(items),
           "reference_s": time.perf_counter() - t0}
    if control is not None:
        ctrl = np.concatenate(ctrl) if ctrl else np.zeros(0)
        out["control_max_logit_gap"] = float(ctrl.max(initial=0.0))
        out["control_top_share"] = (float((ctrl == 0).mean()) if ctrl.size
                                    else 0.0)
    return out


# limit key in a cell's ``check`` -> (number read, how it holds)
LIMITS = {"max_logit_gap": ("max_logit_gap", "<="),
          "min_top_share": ("top_share", ">="),
          "min_tokens": ("tokens_compared", ">=")}


def judge(readings: Dict[str, float], limits: Dict[str, float]):
    """(correct, numbers): each number that the cell limits, beside its
    limit; correct when every one holds."""
    numbers, ok = {}, True
    for key, (name, holds) in LIMITS.items():
        if key not in limits:
            continue
        value, limit = readings[name], limits[key]
        numbers[name] = {"value": value, "limit": limit, "holds": holds}
        ok &= value <= limit if holds == "<=" else value >= limit
    return bool(ok), numbers
