"""Fused exact-plus-error-delta GEMM as a Pallas TPU kernel.

MXU-resident form of the approximate systolic array (see core/error_delta.py):
each (bm, bn, bk) block computes

    o += dot_i8(a, b)                          # exact PE array == the MXU
       + round( sum_r F_r[a] @ G_r[b] )        # rank-r float32 correction

in one kernel. The per-element factor lookups ``F_r = f[a_u, r]`` and
``G_r = g[r, b_u]`` are gathered by the wrapper (`ops.approx_delta_matmul`)
into ``(r, M, K)`` / ``(r, K, N)`` float32 operands that stream block by
block beside A and B: Mosaic lowers no 1-D vector gather, so the kernel
holds only MXU contractions. The correction dots run at
``Precision.HIGHEST`` (full float32 on the MXU) — a bf16 pass would lose the
integer correction.

Rounding happens per K-block: the true block correction is an integer (a sum
of integer E entries), and the float32 noise per block is ~1e-2, so each
rounded block is exact and the int32 accumulation across the K grid introduces
no drift — the kernel is bit-identical to the gather path at the exact rank
for arbitrary K.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 256


def tpu_contract(m: int, n: int, k: int, *, rank: int, span: int = 256,
                 bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                 bk: int = DEFAULT_BK):
    """Static lowering contract mirroring `delta_matmul_fused`'s pallas_call.

    Shape/dtype geometry only (no tracing, no jax) — evaluated by
    `repro.analysis.kernel_audit`; `autotune.gemm_block_plan` prunes block
    candidates through it so the TPU path never launches a geometry the
    auditor rejects.
    """
    from repro.analysis import contracts as C
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    grid = (-(-m // bm), -(-n // bn), -(-k // bk))
    factors = () if not rank else (
        C.OperandSpec("f_a", (rank, m, k), "float32", (rank, bm, bk),
                      lambda i, j, kk: (0, i, kk)),
        C.OperandSpec("g_b", (rank, k, n), "float32", (rank, bk, bn),
                      lambda i, j, kk: (0, kk, j)))
    return C.KernelGeometry(
        kernel="kernels.delta_gemm.delta_matmul_fused",
        grid=grid,
        operands=(
            C.OperandSpec("a", (m, k), "int8", (bm, bk),
                          lambda i, j, kk: (i, kk)),
            C.OperandSpec("b", (k, n), "int8", (bk, bn),
                          lambda i, j, kk: (kk, j)),
            *factors,
            C.OperandSpec("o", (m, n), "int32", (bm, bn),
                          lambda i, j, kk: (i, j)),
        ),
        tag=f"m{m}n{n}k{k}r{rank}bm{bm}bn{bn}bk{bk}",
    )


def _kernel(a_ref, b_ref, *refs, rank: int):
    *factor_refs, o_ref = refs
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # exact base: int8 x int8 -> int32 on the MXU
    acc = jax.lax.dot_general(a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    if rank:
        fa_ref, gb_ref = factor_refs
        corr = jnp.zeros(acc.shape, jnp.float32)
        for rr in range(rank):  # static unroll: rank MXU dots per block
            corr += jax.lax.dot_general(
                fa_ref[rr], gb_ref[rr], (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        acc += jnp.round(corr).astype(jnp.int32)
    o_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def delta_matmul_fused(a_s: jnp.ndarray, b_s: jnp.ndarray,
                       f_a: jnp.ndarray | None = None,
                       g_b: jnp.ndarray | None = None, *,
                       bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                       bk: int = DEFAULT_BK,
                       interpret: bool = False) -> jnp.ndarray:
    """(M, K) x (K, N) -> (M, N) int32 via base matmul + rank-r correction.

    a_s/b_s hold *signed* operand values (int8-representable; ops.py converts
    bit patterns). f_a ``(r, M, K)`` / g_b ``(r, K, N)`` are the gathered
    float32 correction factors (`ops.approx_delta_matmul`); without them the
    kernel is the exact int8 base alone. Shapes must be block multiples
    (ops.approx_delta_matmul pads).
    """
    m, k = a_s.shape
    k2, n = b_s.shape
    assert k == k2, (a_s.shape, b_s.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shapes ({m},{k})x({k},{n}) not multiples of blocks ({bm},{bn},{bk})")
    rank = 0 if f_a is None else f_a.shape[0]
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))]
    args = [a_s.astype(jnp.int8), b_s.astype(jnp.int8)]
    if rank:
        assert f_a.shape == (rank, m, k) and g_b.shape == (rank, k, n), (
            f_a.shape, g_b.shape, a_s.shape, b_s.shape)
        in_specs += [pl.BlockSpec((rank, bm, bk), lambda i, j, kk: (0, i, kk)),
                     pl.BlockSpec((rank, bk, bn), lambda i, j, kk: (0, kk, j))]
        args += [f_a.astype(jnp.float32), g_b.astype(jnp.float32)]
    return pl.pallas_call(
        functools.partial(_kernel, rank=rank),
        grid=(m // bm, n // bn, k // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
