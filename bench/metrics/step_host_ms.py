"""Scheduler: host time of one engine step in the window, in ms. The
engine counts the self time of each phase of its step (``occ["<phase>_ns"]``,
``launch/spans.py``); this is the sum of every phase but the wait for the
sampled tokens (scrub, admit, prepare, dispatch, commit, backoff), differenced
over the window, over the steps that ran in it. With ``step_sync_ms`` it
covers the step that ``engine_step_ms`` times from outside. Nothing to read
where the engine keeps no phase counters or no step ran in the window."""

HOST = ("scrub_ns", "admit_ns", "prepare_ns", "dispatch_ns", "commit_ns",
        "backoff_ns")


def read(ctx):
    steps = len(ctx.steps_in_window())
    if not steps or any(k not in ctx.counters_close for k in HOST):
        return None
    return 1e-6 * sum(ctx.counter(k) for k in HOST) / steps
