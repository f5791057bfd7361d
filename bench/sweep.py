"""Find a cell's knee: the highest offered rate at which the backlog does
not grow over the window. Run once, on the chip, when a cell is defined;
the chosen rate (about 4/5 of the knee) goes into ``workloads/<cell>.json``.

    python3 bench/sweep.py --workload <cell> --seed <n> --rates 2,4,6,8 \\
        [--seconds 30] [--preroll 10]

One process builds the cell once and offers each rate in turn (a fresh
schedule per rate; whatever is left from the previous rate is cancelled
first). The window has to outlast a request's life, or the slots never
fill and a rate below capacity passes for the knee. Per rate it prints the requests due and finished, the output
tokens/s, the p95 time to first token, and the backlog (requests queued for
a slot) over the first and the second half of the window.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def backlog_grows(first: float, second: float) -> bool:
    """The backlog grew when its mean over the window's second half exceeds
    the first half's by more than 2 requests and 20%."""
    return second > first + 2 and second > 1.2 * first


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--preroll", type=float, default=10.0)
    args = ap.parse_args(argv)

    import numpy as np
    from bench.harness import cell, e2e
    from bench.harness.driver import Loop

    srv = cell.setup(cell.Options(args.workload, args.seed, args.seconds))
    eng = srv.eng
    rows = []
    rid0 = 0
    for rate in (float(r) for r in args.rates.split(",")):
        sched = cell.schedule(srv, args.seed, args.preroll + args.seconds + 60,
                              {"kind": "poisson", "rate_per_s": rate})
        for r in sched:
            r.rid += rid0
        rid0 += len(sched) + 1
        t0 = time.perf_counter()
        drv = Loop(eng, sched, t0)
        drv.run_until(t0 + args.preroll)
        t_open = time.perf_counter()
        t_end = t_open + args.seconds
        samples = []

        def tick(now, samples=samples):
            samples.append((now, len(eng.queue)))
        drv.run_until(t_end, tick)
        recs = list(drv.records.values())
        half = t_open + args.seconds / 2
        first = [q for t, q in samples if t < half]
        second = [q for t, q in samples if t >= half]
        m1 = float(np.mean(first)) if first else 0.0
        m2 = float(np.mean(second)) if second else 0.0
        out = e2e.end_to_end(recs, t_open, t_end)
        due = e2e.due_in(recs, t_open, t_end)
        row = {"rate_per_s": rate, "due": len(due),
               "finished": sum(1 for r in due if r.reason),
               **out, "backlog_first_half": m1, "backlog_second_half": m2,
               "grows": backlog_grows(m1, m2)}
        rows.append(row)
        print("sweep " + json.dumps(row), flush=True)
        for rid in [r.rid for r in eng.queue]:
            eng.cancel(rid)
        for s in range(eng.n_slots):
            if eng.slot_req[s] is not None:
                eng.cancel(eng.slot_req[s].rid)
    ok = [r["rate_per_s"] for r in rows if not r["grows"]]
    knee = max(ok) if ok else None
    print(json.dumps({"workload": args.workload, "knee_rate_per_s": knee,
                      "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
