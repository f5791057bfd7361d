"""Scheduler: mean wait in the engine's queue, from ``submit`` (or from a
preemption that put the request back) to its admission into a slot, over the
admissions in the window, in ms (``occ["queue_wait_ns"]`` over
``occ["admitted"]``). Unlike ``queue_wait_p95_ms``, which runs from the due
time and so also holds the client loop's wait for the step in flight to
end, this is the scheduler's share alone. Nothing to read where the engine
keeps no queue counters or admitted nothing in the window."""


def read(ctx):
    if "admitted" not in ctx.counters_close:
        return None
    n = ctx.counter("admitted")
    return None if not n else 1e-6 * ctx.counter("queue_wait_ns") / n
