"""Paged KV pool: share of the prompt tokens admitted in the window that
the prefix cache served from shared blocks (``prefix_tokens_skipped``), in %.
Nothing to read where no prompt was admitted."""


def read(ctx):
    admitted = sum(r.prompt_len for r in ctx.records
                   if r.admit is not None and ctx.t_open <= r.admit < ctx.t_end)
    if not admitted:
        return None
    return 100.0 * ctx.counter("prefix_tokens_skipped") / admitted
