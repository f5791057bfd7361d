"""Flash attention as a Pallas TPU kernel (forward).

The model stack uses the pure-JAX chunked attention (layers.chunked_attention)
everywhere — this kernel is the TPU-native drop-in for the prefill hot spot:
grid (batch*heads, q_blocks), online softmax over K/V blocks streamed through
VMEM, causal + sliding-window masking computed from block indices so fully
masked K blocks are skipped via `pl.when`.

Ragged lengths: callers pad S_kv up to a multiple of the block size, and the
padded K rows are zeros — under causal self-attention they land at positions
the causal mask already hides, but non-causal (or cross-attention) padded rows
score ``s = 0`` and would contribute ``exp(0 - m)`` mass to every softmax.
``kv_valid_len`` (scalar or per-batch ``(B,)``) masks key positions ``>= len``
explicitly and clamps the K-block scan to the last live block, so the result
matches the unpadded jnp reference bit-for-bit. Rows whose mask admits no key
at all (``kv_valid_len == 0``) are out of contract, as is any
``kv_valid_len > S_kv``.

Block shapes default to MXU/VPU-aligned (128 q rows x 128 kv cols x head_dim).
Validated in interpret mode against layers.chunked_attention / a naive oracle
(tests/test_flash_kernel.py).
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
BIG_NEG = -2.3819763e38


def tpu_contract(b: int, h: int, sq: int, skv: int, d: int, *,
                 dtype: str = "float32", bq: int = DEFAULT_BQ,
                 bk: int = DEFAULT_BK):
    """Static lowering contract mirroring `flash_attention`'s pallas_call.

    Shape/dtype geometry only (no tracing, no jax). Note the kernel holds a
    row's *entire* padded KV in VMEM per grid cell (the K scan is an
    in-kernel fori_loop, not a grid axis), so the auditable envelope is
    bounded by ``2 * 2 * skv * d * itemsize <= VMEM`` — the auditor flags
    longer contexts as vmem-overflow (see docs/analysis.md).
    """
    from repro.analysis import contracts as C
    bh = b * h
    return C.KernelGeometry(
        kernel="kernels.flash_attention.flash_attention",
        grid=(bh, -(-sq // bq)),
        operands=(
            C.OperandSpec("q", (bh, sq, d), dtype, (1, bq, d),
                          lambda bhi, qi, *_: (bhi, qi, 0)),
            C.OperandSpec("k", (bh, skv, d), dtype, (1, skv, d),
                          lambda bhi, qi, *_: (bhi, 0, 0)),
            C.OperandSpec("v", (bh, skv, d), dtype, (1, skv, d),
                          lambda bhi, qi, *_: (bhi, 0, 0)),
            C.OperandSpec("o", (bh, sq, d), dtype, (1, bq, d),
                          lambda bhi, qi, *_: (bhi, qi, 0)),
        ),
        scalar_prefetch=(C.ScalarSpec("kv_valid_len", (b,), "int32"),),
        tag=f"b{b}h{h}sq{sq}skv{skv}d{d}{dtype}bq{bq}bk{bk}",
    )


def _kernel(kvl_ref, q_ref, k_ref, v_ref, o_ref, *, h: int, bq: int, bk: int,
            skv: int, causal: bool, window: int, softcap: float,
            scale: float):
    bhi = pl.program_id(0)
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale            # (bq, d)
    n_kb = skv // bk
    kvl = kvl_ref[bhi // h]
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    def body(kb, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(kb * bk, bk), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = kpos < kvl
        if causal:
            delta = qpos - kpos
            valid &= (delta >= 0)
            if window > 0:
                valid &= (delta < window)
        s = jnp.where(valid, s, BIG_NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    d = q_ref.shape[-1]
    init = (jnp.zeros((bq, d), jnp.float32),
            jnp.full((bq,), BIG_NEG, jnp.float32),
            jnp.zeros((bq,), jnp.float32))
    # K blocks past the live length contribute nothing — skip them. Blocks
    # strictly after this Q block are likewise dead under the causal mask.
    last_kb = jnp.minimum(n_kb, (kvl + bk - 1) // bk).astype(jnp.int32)
    if causal:
        last_kb = jnp.minimum(
            last_kb, (qi + 1) * bq // bk + (1 if bq % bk else 0))
    acc, m, l = jax.lax.fori_loop(0, last_kb, body, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "bq", "bk", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    kv_valid_len: Optional[Union[int, jnp.ndarray]] = None,
                    causal: bool = True, window: int = 0, softcap: float = 0.0,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = False) -> jnp.ndarray:
    """q/k/v: (B, H, S, D) with S a multiple of the block sizes (ops-level
    wrappers pad). MQA/GQA callers broadcast KV heads before the call.
    ``kv_valid_len``: live key count per batch row (scalar or ``(B,)``) when
    S_kv carries right-padding; ``None`` means every key row is live."""
    b, h, sq, d = q.shape
    skv = k.shape[2]
    assert sq % bq == 0 and skv % bk == 0, (sq, skv, bq, bk)
    bh = b * h
    qf = q.reshape(bh, sq, d)
    kf = k.reshape(bh, skv, d)
    vf = v.reshape(bh, skv, d)
    if kv_valid_len is None:
        kv_valid_len = skv
    kvl = jnp.broadcast_to(
        jnp.asarray(kv_valid_len, jnp.int32).reshape(-1), (b,))
    grid = (bh, sq // bq)
    kern = functools.partial(_kernel, h=h, bq=bq, bk=bk, skv=skv,
                             causal=causal, window=window, softcap=softcap,
                             scale=d ** -0.5)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda bhi, qi, *_: (bhi, qi, 0)),
                pl.BlockSpec((1, skv, d), lambda bhi, qi, *_: (bhi, 0, 0)),
                pl.BlockSpec((1, skv, d), lambda bhi, qi, *_: (bhi, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, d),
                                   lambda bhi, qi, *_: (bhi, qi, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(kvl, qf, kf, vf)
    return out.reshape(b, h, sq, d)
