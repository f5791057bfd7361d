"""Chunk step: the engine's wait for each step's sampled tokens on the host
(its ``sync`` phase, ``occ["sync_ns"]``, ``launch/spans.py``), differenced
over the window, per step that ran in it, in ms. The engine sends its next
step only after this wait, so it is the device's step time less what the
device did while the host was still in the step's other phases. Nothing to
read where the engine keeps no phase counters or no step ran in the
window."""


def read(ctx):
    steps = len(ctx.steps_in_window())
    if not steps or "sync_ns" not in ctx.counters_close:
        return None
    return 1e-6 * ctx.counter("sync_ns") / steps
