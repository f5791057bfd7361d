"""End-to-end metrics from the client loop's records. Pure arithmetic over host
timestamps, so it is checked on the CPU with synthetic event logs.

A request counts for the window when it was due in ``[t_open, t_end)``.
Tokens count when they became visible on the host in ``(t_open, t_end]``.
The first-token tail takes each such request's first token whenever it
came: the run serves on past ``t_end`` until each has one, or until
``t_wait``; one with none by then enters it with ``t_wait - due``. The
per-token tail takes, for each such request with two tokens or more by
``t_end``, its mean gap between the tokens seen by then:
``(t_last - t_first) / (n - 1)``, finished or not.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional

import numpy as np

OK_REASONS = (None, "length", "eos")


def p95(values) -> Optional[float]:
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, 95)) if v.size else None


def _n_at(rec, t: float) -> int:
    n = 0
    for tv, nv in rec.vis:
        if tv > t:
            break
        n = nv
    return n


def _last_at(rec, t: float) -> Optional[float]:
    last = None
    for tv, _ in rec.vis:
        if tv > t:
            break
        last = tv
    return last


def due_in(records: Iterable, t_open: float, t_end: float) -> List:
    return [r for r in records if t_open <= r.due < t_end]


def output_tokens(records: Iterable, t_open: float, t_end: float) -> int:
    return sum(_n_at(r, t_end) - _n_at(r, t_open) for r in records)


def ttft_s(records: Iterable, t_open: float, t_end: float,
           t_wait: Optional[float] = None) -> List[float]:
    t_wait = t_end if t_wait is None else t_wait
    out = []
    for r in due_in(records, t_open, t_end):
        first = r.vis[0][0] if r.vis else None
        out.append((first if first is not None and first <= t_wait
                    else t_wait) - r.due)
    return out


def tpot_s(records: Iterable, t_open: float, t_end: float) -> List[float]:
    out = []
    for r in due_in(records, t_open, t_end):
        n = _n_at(r, t_end)
        if n > 1:
            out.append((_last_at(r, t_end) - r.vis[0][0]) / (n - 1))
    return out


def queue_wait_s(records: Iterable, t_open: float, t_end: float
                 ) -> List[float]:
    return [(r.admit if r.admit is not None and r.admit <= t_end else t_end)
            - r.due for r in due_in(records, t_open, t_end)]


def end_to_end(records: Iterable, t_open: float, t_end: float,
               t_wait: Optional[float] = None
               ) -> Dict[str, Optional[float]]:
    records = list(records)
    span = t_end - t_open
    ttft = ttft_s(records, t_open, t_end, t_wait)
    tpot = tpot_s(records, t_open, t_end)
    return {
        "output_tok_per_s": output_tokens(records, t_open, t_end) / span,
        "ttft_p95_ms": None if not ttft else 1e3 * p95(ttft),
        "tpot_p95_ms": None if not tpot else 1e3 * p95(tpot),
    }


def attempted_failed(records: Iterable, t_open: float, t_end: float):
    due = due_in(records, t_open, t_end)
    return len(due), sum(1 for r in due if r.reason not in OK_REASONS)
