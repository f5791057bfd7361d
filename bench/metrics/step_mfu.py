"""Whole step: the model's operations for the work done in the window over
the window times the chip's int8 peak, in %. Both configurations' matrix
products are 8-bit products on the PE.

Operations: 2 per GEMM weight for every prompt or decode token the steps
processed, 2 per LM-head weight for every output token, and 4 * heads *
head_dim per layer for each processed token times its context length
(QK^T and PV). Work the engine pads (idle slots, chunk padding) is not
counted. The context of a request's first step is taken as if its chunk
were full: at most one chunk per request is counted at a context longer
than it had, a few tokens in hundreds."""
from bench.harness import e2e


def gemm_weights_per_layer(cfg):
    d, hd = cfg.d_model, cfg.hd
    return (d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd
            + cfg.n_heads * hd * d + 3 * d * cfg.d_ff)


def read(ctx):
    if not ctx.peaks:
        return None
    cfg = ctx.cfg
    steps = ctx.steps_in_window()
    tokens = sum(s.tokens for s in steps)
    context = sum(s.context for s in steps)
    out_tokens = e2e.output_tokens(ctx.records, ctx.t_open, ctx.t_end)
    ops = (2.0 * cfg.n_layers * gemm_weights_per_layer(cfg) * tokens
           + 2.0 * cfg.d_model * cfg.vocab_size * out_tokens
           + 4.0 * cfg.n_layers * cfg.n_heads * cfg.hd * context)
    return 100.0 * ops / ((ctx.t_end - ctx.t_open) * ctx.peaks["int8_ops_per_s"])
