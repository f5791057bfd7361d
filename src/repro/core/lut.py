"""LUT-based fast functional model of the approximate GEMM.

The fused bit-level oracle (`emulate.matmul_oracle`) is exact-to-the-netlist but
slow. For application-scale workloads we factor the approximation:

    approx(a*b + c)  ≈  approx_product(a, b) + c        ("multiplier-approx model")

where approx_product is the 2^N x 2^N table of PE outputs at c = 0. This keeps the
approximate-multiplier error exactly and drops only the (small) error component the
fused accumulator contributes; tests quantify the residual against the oracle.

Two execution strategies:

* `lut_matmul`      — direct gather: out[m,n] = sum_k T[a[m,k], b[k,n]] (VPU path;
                      also the reference for the Pallas approx kernel).
* `onehot_matmul`   — beyond-paper TPU trick: one-hot-encode A against the table so
                      the *approximate* GEMM runs on the *exact* MXU:
                        out = onehot(A) @ T_B, with T_B[k*V + v, n] = T[v, b[k,n]].
                      256x FLOP inflation, but MXU FLOPs are ~100x cheaper than VPU
                      gathers — and for fixed weights T_B is precomputed once.

HBM footprint of `onehot_matmul`: the one-hot operand is (M, K*V) — with V=256
and bf16 encoding that is 512*K bytes per output row (it was 1024*K as float32),
plus the (K*V, N) float32 T_B. The 0/1 one-hot is exact in bf16 and the f32
accumulation is unchanged, so bf16 halves the dominant HBM term with no loss.
For activations that change every call, `kernels/ops.approx_delta_matmul`
(core/error_delta.py) reaches the MXU with only rank-r (r ~ 7 at k=4) inflation
instead of 256x and is the preferred fast path; `onehot_matmul` remains useful
when B is fixed and T_B amortizes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .emulate import product_table_jnp


def table_gather_matmul(a_u: jnp.ndarray, b_u: jnp.ndarray,
                        flat_table: jnp.ndarray, *, span: int) -> jnp.ndarray:
    """Gather-GEMM over any (span*span,) table: out[m,n] = sum_kk T[a[m,kk], b[kk,n]].

    The one gather loop shared by the LUT model, the error-delta defect
    cancellation, and the kernel references; accumulates in the table's dtype.
    """
    def one_k(carry, inputs):
        a_col, b_row = inputs                       # (M,), (N,)
        idx = a_col[:, None] * span + b_row[None, :]
        carry = carry + jnp.take(flat_table, idx, axis=0)
        return carry, None

    init = jnp.zeros((a_u.shape[0], b_u.shape[1]), flat_table.dtype)
    out, _ = jax.lax.scan(one_k, init, (a_u.T, b_u))
    return out


def lut_matmul(a, b, *, n_bits: int = 8, k: int = 4, signed: bool = True,
               acc_bits: int = 24):
    """(M,K) x (K,N) approximate GEMM via product-table gathers, int32 accumulate."""
    span = 1 << n_bits
    mask = span - 1
    a_u = jnp.asarray(a, jnp.int32) & mask          # (M, K) unsigned patterns
    b_u = jnp.asarray(b, jnp.int32) & mask          # (K, N)
    flat = product_table_jnp(n_bits, k, signed, acc_bits, flat=True)
    return table_gather_matmul(a_u, b_u, flat, span=span)


def build_onehot_weights(b, *, n_bits: int = 8, k: int = 4, signed: bool = True,
                         acc_bits: int = 24) -> jnp.ndarray:
    """Precompute T_B (K*V, N) for `onehot_matmul` from weight matrix b (K, N).

    Pure-jnp gather into the cached device table, so it is traceable: the
    unbound ``approx_onehot`` model path rebuilds T_B under jit/scan (the cost
    ``core.gemm.bind`` amortizes away), while prepared operands store it once.
    """
    table = product_table_jnp(n_bits, k, signed, acc_bits)  # (V, V) device
    span = 1 << n_bits
    b_u = jnp.asarray(b, jnp.int32) & (span - 1)    # (K, N)
    t_b = jnp.take(table, b_u, axis=1)              # (V, K, N)
    t_b = jnp.transpose(t_b, (1, 0, 2))             # (K, V, N)
    kk, _, nn = t_b.shape
    return t_b.reshape(kk * span, nn).astype(jnp.float32)


def onehot_matmul(a, t_b, *, n_bits: int = 8):
    """Approximate GEMM on the MXU: onehot(A) (M, K*V) @ T_B (K*V, N).

    The one-hot operand is bf16 (0/1 is exact in bf16, halving its HBM/VMEM
    footprint vs float32); accumulation stays float32 so table-value sums up to
    2^24 remain exact, as before.
    """
    span = 1 << n_bits
    a_u = jnp.asarray(a, jnp.int32) & (span - 1)    # (M, K)
    m, kk = a_u.shape
    onehot = jax.nn.one_hot(a_u, span, dtype=jnp.bfloat16)  # (M, K, V)
    out = jax.lax.dot_general(                              # exact MXU matmul
        onehot.reshape(m, kk * span), t_b, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return out.astype(jnp.int32)
