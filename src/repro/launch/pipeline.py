"""Pipeline parallelism over the `pod` axis (GPipe-style).

The multi-pod mesh's `pod` axis defaults to data-parallel; this module provides
the alternative: layer-stage parallelism. Stacked layer params (L, ...) are
split into (n_stages, L/n_stages, ...) and sharded over `pod`; the step runs
under `shard_map`, streaming M microbatches through the stages with
`ppermute` hops between neighbours — a scan over M + S - 1 pipeline ticks, so
each pod computes its stage's layers only, with the classic (S-1)/(M+S-1)
bubble. Because `ppermute` is differentiable (its transpose is the reverse
permutation), `jax.grad` through this forward yields the pipelined backward
automatically, with GPipe's O(M) activation stash.

This is the scale-out path for models too deep for one pod's HBM at 1000+
nodes; elastic restart reshards the (S, L/S, ...) split to any stage count
that divides L (the checkpoint layout stays stage-agnostic: plain (L, ...)).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def split_stages(stacked, n_stages: int):
    """(L, ...) stacked layer params -> (S, L/S, ...)."""
    def one(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])
    return jax.tree.map(one, stacked)


def merge_stages(staged):
    return jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), staged)


def make_pipelined_apply(stage_fn: Callable, n_stages: int, mesh: Mesh,
                         axis: str = "pod"):
    """Build `apply(staged_params, x_micro) -> y_micro`.

    stage_fn(stage_params, x): one stage's layers, (b, s, d) -> (b, s, d).
    x_micro: (M, b, s, d) microbatches, replicated over `axis`. The returned
    apply runs the GPipe schedule and returns (M, b, s, d) final activations.
    """
    def pipelined(staged_params, x_micro):
        sp = jax.tree.map(lambda a: a[0], staged_params)   # my stage's params
        m = x_micro.shape[0]
        ticks = m + n_stages - 1
        idx = jax.lax.axis_index(axis)
        fwd = [(i, i + 1) for i in range(n_stages - 1)]

        def step(carry, t):
            buf, outs = carry                      # buf: activation entering me
            feed = jnp.clip(t, 0, m - 1)
            my_in = jnp.where(idx == 0, x_micro[feed], buf)
            active = (t - idx >= 0) & (t - idx < m)
            out = stage_fn(sp, my_in)
            out = jnp.where(active, out, jnp.zeros_like(out))
            done = jnp.clip(t - (n_stages - 1), 0, m - 1)
            is_done = (idx == n_stages - 1) & (t >= n_stages - 1)
            outs = jax.lax.cond(is_done,
                                lambda o: o.at[done].set(out),
                                lambda o: o, outs)
            nxt = (jax.lax.ppermute(out, axis, fwd)
                   if n_stages > 1 else jnp.zeros_like(out))
            return (nxt, outs), None

        init = (jnp.zeros_like(x_micro[0]), jnp.zeros_like(x_micro))
        (_, outs), _ = jax.lax.scan(step, init,
                                    jnp.arange(ticks, dtype=jnp.int32))
        if n_stages > 1:   # only the last stage wrote -> psum broadcasts it
            outs = jax.lax.psum(outs, axis)
        return outs

    def apply(staged_params, x_micro):
        in_specs = (jax.tree.map(lambda _: P(axis), staged_params), P())
        return jax.shard_map(pipelined, mesh=mesh, in_specs=in_specs,
                             out_specs=P(), check_vma=False)(staged_params,
                                                             x_micro)
    return apply
