"""Scheduler: share of slot-steps that held a live request over the window
(the engine's ``occ`` counters, as differences), in %."""


def read(ctx):
    slots = ctx.counter("slot_steps")
    return None if not slots else 100.0 * ctx.counter("slot_active_steps") / slots
