"""Chunk step: mean host wall time of one ``ServeEngine.step()`` that
started in the window. Each step ends in the engine's device-to-host sync
of its sampled tokens, so this is the step as the scheduler sees it."""


def read(ctx):
    steps = ctx.steps_in_window()
    if not steps:
        return None
    return 1e3 * sum(s.end - s.start for s in steps) / len(steps)
