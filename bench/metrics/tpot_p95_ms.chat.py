"""Chunk step as the client sees it: 95th percentile, over the requests due
in the window with two tokens or more by its close, of each one's mean gap
between the tokens seen by then (host clock). A saturated cell's tokens/s
is the same step time seen from the other side. A stall of the machine
inflates every request decoding through it, which is why this is not an
end-to-end metric (PERF.md, section 2)."""
from bench.harness import e2e


def read(ctx):
    gaps = e2e.tpot_s(ctx.records, ctx.t_open, ctx.t_end)
    return None if not gaps else 1e3 * e2e.p95(gaps)
