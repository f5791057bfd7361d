"""Scheduler: 95th percentile of the wait from a request's due time to the
first engine step in which it holds a slot (host clock), over requests due
in the window; one not admitted by the end waits until the end."""
from bench.harness import e2e


def read(ctx):
    waits = e2e.queue_wait_s(ctx.records, ctx.t_open, ctx.t_end)
    return None if not waits else 1e3 * e2e.p95(waits)
