"""Public jit'd wrappers for the Pallas kernels.

Handles: arbitrary-shape padding to block multiples, signed->bit-pattern
conversion for the LUT kernel, padding-contribution correction (padded K rows
contribute T[0,0] per row, subtracted after the call), and the platform
choice of how a kernel runs: compiled by Mosaic on a TPU, and in Pallas
interpret mode everywhere else (the CPU test path). Nothing else selects
interpret mode, so no kernel is ever interpreted on the chip.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import error_delta, lut
from . import approx_gemm, delta_gemm, systolic_gemm


def on_tpu() -> bool:
    """True when a TPU backs JAX: kernels then lower through Mosaic; on any
    other platform they run in interpret mode."""
    return jax.default_backend() == "tpu"


def _pad_to(x: jnp.ndarray, mult_r: int, mult_c: int) -> jnp.ndarray:
    r, c = x.shape
    pr = (-r) % mult_r
    pc = (-c) % mult_c
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


def _blocks(dim: int, pref: int, align: int) -> int:
    """Largest block <= pref that is a multiple of `align` covering dim decently."""
    if dim <= align:
        return dim if dim > 0 else align
    b = min(pref, dim)
    return max(align, (b // align) * align)


def _tpu_blocks(m: int, n: int, k: int, kernel: str, prefs, *, rank: int = 0):
    """TPU block picker: `_blocks` alignment arithmetic pruned through the
    static lowering contract, so the non-interpret path never launches a
    geometry `repro.analysis.kernel_audit` rejects."""
    from repro.analysis.kernel_audit import gemm_block_plan
    return gemm_block_plan(m, n, k, kernel=kernel, rank=rank, prefs=prefs)


def systolic_matmul(a: jnp.ndarray, b: jnp.ndarray, *, bm: int | None = None,
                    bn: int | None = None, bk: int | None = None) -> jnp.ndarray:
    """Exact int8 GEMM (int32 accumulate) for arbitrary (M, K) x (K, N)."""
    interpret = not on_tpu()
    m, k = a.shape
    _, n = b.shape
    bm = bm or systolic_gemm.DEFAULT_BM
    bn = bn or systolic_gemm.DEFAULT_BN
    bk = bk or systolic_gemm.DEFAULT_BK
    # in interpret mode alignment is irrelevant; on TPU the block plan is
    # MXU-aligned and contract-pruned
    if interpret:
        bm_, bn_, bk_ = (_blocks(m, bm, 8), _blocks(n, bn, 8),
                         _blocks(k, bk, 8))
    else:
        bm_, bn_, bk_ = _tpu_blocks(m, n, k, "systolic", (bm, bn, bk))
    a_p = _pad_to(a, bm_, bk_)
    b_p = _pad_to(b, bk_, bn_)
    out = systolic_gemm.systolic_matmul(a_p, b_p, bm=bm_, bn=bn_, bk=bk_,
                                        interpret=interpret)
    return out[:m, :n]


def approx_matmul(a: jnp.ndarray, b: jnp.ndarray, *, k: int = 4, n_bits: int = 8,
                  acc_bits: int = 24, signed: bool = True,
                  bm: int | None = None, bn: int | None = None,
                  bk: int | None = None) -> jnp.ndarray:
    """Approximate GEMM at factor k for arbitrary shapes (signed operands).

    The per-element product-table gather has no Mosaic lowering, so this
    backend runs only off the chip; on a TPU ``approx_delta`` computes the
    same bits on the MXU."""
    if on_tpu():
        raise NotImplementedError(
            "approx_lut has no TPU lowering (Mosaic cannot gather from the "
            "product table); use backend='approx_delta', which is "
            "bit-identical to approx_lut at its default (exact) rank")
    span = 1 << n_bits
    mask = span - 1
    m, kd = a.shape
    _, n = b.shape
    table = approx_gemm.make_table(k, n_bits=n_bits, signed=signed,
                                   acc_bits=acc_bits)
    a_u = jnp.asarray(a, jnp.int32) & mask
    b_u = jnp.asarray(b, jnp.int32) & mask
    bm = bm or approx_gemm.DEFAULT_BM
    bn = bn or approx_gemm.DEFAULT_BN
    bk = bk or approx_gemm.DEFAULT_BK
    bm_, bn_, bk_ = _blocks(m, bm, 8), _blocks(n, bn, 8), _blocks(kd, bk, 8)
    a_p = _pad_to(a_u, bm_, bk_)
    b_p = _pad_to(b_u, bk_, bn_)
    out = approx_gemm.approx_matmul_lut(a_p, b_p, table, span=span, bm=bm_,
                                        bn=bn_, bk=bk_, interpret=True)
    out = out[:m, :n]
    k_pad = a_p.shape[1] - kd
    if k_pad:
        # padded K rows each contribute T[0,0] (nonzero for deep approximation)
        t00 = table[0]
        out = out - k_pad * t00
    return out


def approx_delta_matmul(a: jnp.ndarray, b: jnp.ndarray, *, k: int = 4,
                        n_bits: int = 8, acc_bits: int = 24, signed: bool = True,
                        rank: int | None = None, tol: float | None = None,
                        apply_residual: bool = True,
                        bm: int | None = None, bn: int | None = None,
                        bk: int | None = None) -> jnp.ndarray:
    """Approximate GEMM via the exact-plus-error-delta decomposition.

    Computes ``A_s @ B_s + round(F_A @ G_B)`` (see core/error_delta.py): one
    exact int8 MXU matmul plus a rank-r float32 correction matmul, fused in a
    single Pallas kernel. At the default rank (``rank_for_exact``) the result
    is bit-identical to ``approx_matmul`` / ``lut.lut_matmul``; a truncated
    ``rank``/``tol`` trades correction FLOPs for bounded extra error, which
    ``apply_residual=True`` cancels again via a gather pass over the integer
    residual table.
    """
    interpret = not on_tpu()
    fac = error_delta.delta_factors(n_bits, k, signed, acc_bits, rank=rank,
                                    tol=tol)
    span = 1 << n_bits
    mask = span - 1
    half = span >> 1
    m, kd = a.shape
    _, n = b.shape
    a_u = jnp.asarray(a, jnp.int32) & mask
    b_u = jnp.asarray(b, jnp.int32) & mask
    if not signed:
        # unsigned 8-bit values don't fit the kernel's int8 base dot; the
        # pure-jnp reference handles that (rare, off-paper) configuration.
        return error_delta.delta_matmul_ref(a, b, k=k, n_bits=n_bits,
                                            signed=signed, acc_bits=acc_bits,
                                            rank=rank, tol=tol,
                                            apply_residual=apply_residual)
    a_s = (a_u ^ half) - half                       # sign-extended operand values
    b_s = (b_u ^ half) - half
    bm = bm or delta_gemm.DEFAULT_BM
    bn = bn or delta_gemm.DEFAULT_BN
    bk = bk or delta_gemm.DEFAULT_BK
    if interpret:
        bm_, bn_, bk_ = (_blocks(m, bm, 8), _blocks(n, bn, 8),
                         _blocks(kd, bk, 8))
    else:
        bm_, bn_, bk_ = _tpu_blocks(m, n, kd, "delta", (bm, bn, bk),
                                    rank=fac.rank)
    a_p = _pad_to(a_s, bm_, bk_)
    b_p = _pad_to(b_s, bk_, bn_)
    exact_cancel = apply_residual and not fac.exact
    if exact_cancel:
        # truncated rank, bit-exactness requested: per-block rounding does not
        # commute with the defect cancellation, so run the fused kernel for the
        # base only and round correction + defect once (see error_delta docs)
        base = delta_gemm.delta_matmul_fused(
            a_p, b_p, bm=bm_, bn=bn_, bk=bk_, interpret=interpret)[:m, :n]
        corr = (error_delta._correction(a_u, b_u, fac) if fac.rank
                else jnp.zeros((m, n), jnp.float32))
        corr = corr + error_delta.defect_gather_matmul(a_u, b_u, fac)
        return base + jnp.round(corr).astype(jnp.int32)
    f_a = g_b = None
    if fac.rank:
        f_flat, g_flat = error_delta.factor_tables_jnp(n_bits, k, signed,
                                                       acc_bits, rank=rank,
                                                       tol=tol)
        # the per-element factor lookups happen here: the kernel itself
        # holds MXU contractions only (padded zeros look up pattern 0)
        bits = error_delta.select_bits(n_bits, k)
        f_a = error_delta.moving_factor(
            a_p & mask, f_flat.reshape(span, fac.rank).T, bits)  # (r, M, K)
        g_b = error_delta.moving_factor(
            b_p & mask, g_flat.reshape(fac.rank, span), bits)    # (r, K, N)
    out = delta_gemm.delta_matmul_fused(a_p, b_p, f_a, g_b, bm=bm_, bn=bn_,
                                        bk=bk_, interpret=interpret)
    out = out[:m, :n]
    k_pad = a_p.shape[1] - kd
    if k_pad and fac.rank:
        # padded K rows contribute 0 to the base and recon(E[0,0]) each to the
        # per-block-rounded correction (== E[0,0] exactly at the exact rank)
        out = out - k_pad * int(np.round(float(fac.f[0] @ fac.g[:, 0])))
    return out


# --- weight-stationary prepared operands + batched app workloads ------------

@dataclasses.dataclass(frozen=True)
class PreparedOperand:
    """A fixed GEMM operand with its backend-specific precompute done once.

    Built by ``prepare_operand`` (or ``core.gemm.prepare_weights``) for weight
    matrices that are reused across calls — the DCT matrix, convolution
    kernels, model layer weights. ``side`` says which operand of the product
    the matrix is: ``"right"`` for ``x @ W``, ``"left"`` for ``W @ x`` (the
    approximate product table is not symmetric, so the two are distinct).

    Precomputes per backend: ``approx_delta`` stores the rank-r ``G_B`` /
    ``F_A`` correction factor (core/error_delta.PreparedDelta);
    ``approx_onehot`` stores the (K·2^N, N) ``T_B`` table (right side only —
    a fixed left operand precomputes nothing, T_B then depends on the moving
    operand). The remaining backends are stateless and store only the values.

    ``scale`` is the dequantization scale attached by ``core.gemm`` when the
    operand was prepared from *float* weights (per-output-channel): its
    presence switches ``gemm.dot`` into float mode (quantize the moving
    operand only, dequantize with ``moving_scale * scale``).

    ``abft`` is the clean-weight checksum metadata (``core.abft.AbftMeta``)
    attached by ``core.gemm.prepare_weights``: row/column sums of ``values``
    plus a bit-level fingerprint of the derived leaves. Always attached (it
    is cheap) so the pytree structure does not depend on ``GemmPolicy.guard``;
    ``gemm.dot`` only *checks* it when the policy asks.

    Registered as a JAX pytree — arrays are children, the backend/shape-free
    metadata is static aux data — so prepared operands (and whole bound
    parameter pytrees containing them) can be jit arguments and ``lax.scan``
    xs. Leaves may carry extra *leading* stack dimensions (stacked per-layer
    or per-expert preparations built by ``core.gemm.bind``); 2-D consumers
    slice them off via ``lax.scan`` / ``jax.tree.map`` indexing first.
    """
    backend: str
    side: str
    k: int
    n_bits: int
    acc_bits: int
    values: jnp.ndarray
    delta: Optional[error_delta.PreparedDelta] = None
    t_b: Optional[jnp.ndarray] = None
    rank: Optional[int] = None
    tol: Optional[float] = None
    scale: Optional[jnp.ndarray] = None
    abft: Optional[object] = None


jax.tree_util.register_pytree_node(
    PreparedOperand,
    lambda p: ((p.values, p.delta, p.t_b, p.scale, p.abft),
               (p.backend, p.side, p.k, p.n_bits, p.acc_bits, p.rank, p.tol)),
    lambda aux, ch: PreparedOperand(aux[0], aux[1], aux[2], aux[3], aux[4],
                                    ch[0], ch[1], ch[2], aux[5], aux[6],
                                    ch[3], ch[4]))


def prepare_operand(w, *, backend: str, k: int = 4, n_bits: int = 8,
                    acc_bits: int = 24, side: str = "right",
                    rank: int | None = None,
                    tol: float | None = None,
                    restrict: bool = True) -> PreparedOperand:
    """Precompute whatever ``backend`` can amortize for fixed operand ``w``.

    ``restrict=False`` disables the weight-restricted delta rank so prepared
    operands of different weights share one pytree structure (see
    ``error_delta.prepare_delta``). ``w`` may carry leading stack dims
    (``restrict=False`` only): the whole stack is prepared in one vectorized
    pass and every leaf of the result keeps the stack dims in front.
    """
    if side not in ("right", "left"):
        raise ValueError(f"side must be 'right' or 'left', got {side!r}")
    w = jnp.asarray(w, jnp.int32)
    if w.ndim < 2:
        raise ValueError(f"prepared operand must be >= 2D, got shape {w.shape}")
    if w.ndim > 2 and restrict:
        raise ValueError(
            f"stacked preparation (shape {w.shape}) requires restrict=False "
            "so every slice shares one rank/pytree structure")
    delta = t_b = None
    if backend == "approx_delta":
        delta = error_delta.prepare_delta(w, side=side, n_bits=n_bits, k=k,
                                          acc_bits=acc_bits, rank=rank, tol=tol,
                                          restrict=restrict)
    elif backend == "approx_onehot" and side == "right":
        build = functools.partial(lut.build_onehot_weights, n_bits=n_bits,
                                  k=k, acc_bits=acc_bits)
        if w.ndim == 2:
            t_b = build(w)
        else:
            lead = w.shape[:-2]
            flat = jax.vmap(build)(w.reshape((-1,) + w.shape[-2:]))
            t_b = flat.reshape(lead + flat.shape[1:])
    return PreparedOperand(backend, side, k, n_bits, acc_bits, w, delta, t_b,
                           rank, tol)


def prepared_matmul(x, prep: PreparedOperand) -> jnp.ndarray:
    """2D integer GEMM of moving operand ``x`` against a prepared operand."""
    x = jnp.asarray(x, jnp.int32)
    a, b = (x, prep.values) if prep.side == "right" else (prep.values, x)
    backend = prep.backend
    if backend == "exact":
        return jnp.matmul(a, b)
    if backend == "mxu_int8":
        return systolic_matmul(a, b)
    if backend == "approx_lut":
        return approx_matmul(a, b, k=prep.k, n_bits=prep.n_bits,
                             acc_bits=prep.acc_bits)
    if backend == "approx_oracle":
        from repro.core import emulate
        return emulate.matmul_oracle(a, b, n_bits=prep.n_bits, k=prep.k,
                                     acc_bits=prep.acc_bits)
    if backend == "approx_onehot":
        t_b = prep.t_b
        if t_b is None:     # left-fixed operand: T_B depends on the moving b
            t_b = lut.build_onehot_weights(b, n_bits=prep.n_bits,
                                           k=prep.k, acc_bits=prep.acc_bits)
        return lut.onehot_matmul(a, t_b, n_bits=prep.n_bits)
    if backend == "approx_delta":
        return error_delta.delta_matmul_prepared(x, prep.delta)
    raise ValueError(f"unknown backend {backend!r}")


def batched_app_matmul(matmul2d: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
                       a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Pad-and-batch shim: map batched app GEMMs onto the 2D kernel wrappers.

    * ``(..., M, K) x (K, N)`` — batch flattened into the M (rows) dimension.
    * ``(M, K) x (..., K, N)`` — batch flattened into the N (columns)
      dimension. The operand order is preserved (no transpose trick): the
      approximate product table is not symmetric, so ``T @ X`` computed as
      ``(X^T @ T^T)^T`` would change the approximate bits.

    The 2D wrappers then pad to block multiples, so ``(N, 8, 8)`` workloads
    (DCT blocks, im2col tiles) run on the same Pallas kernels as big GEMMs.
    At most one operand may carry batch dimensions.
    """
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    if a.ndim == 2 and b.ndim == 2:
        return matmul2d(a, b)
    if a.ndim > 2 and b.ndim > 2:
        raise ValueError(
            f"at most one batched operand, got shapes {a.shape} x {b.shape}")
    if b.ndim == 2:                                   # (..., M, K) x (K, N)
        lead = a.shape[:-2]
        m, kd = a.shape[-2:]
        out = matmul2d(a.reshape(-1, kd), b)
        return out.reshape(*lead, m, b.shape[-1])
    lead = b.shape[:-2]                               # (M, K) x (..., K, N)
    kd, n = b.shape[-2:]
    b2 = jnp.moveaxis(b.reshape(-1, kd, n), 1, 0).reshape(kd, -1)
    out = matmul2d(a, b2)                             # (M, batch*N)
    m = a.shape[0]
    return jnp.moveaxis(out.reshape(m, -1, n), 0, 1).reshape(*lead, m, n)


def grouped_matmul(matmul2d: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
                   a: jnp.ndarray, b) -> jnp.ndarray:
    """Grouped GEMM shim: ``(G, M, K) x (G, K, N) -> (G, M, N)``.

    Both operands carry the *same* leading group dimension (MoE expert
    einsums: one weight matrix per expert). The 2D kernel is ``jax.vmap``-ed
    over the group axis — each group keeps its own quantization/preparation
    (which a flattening shim could not express) while the jaxpr stays O(1)
    in the expert count instead of unrolling G subgraphs per GEMM. ``b`` may
    be a raw ``(G, K, N)`` array or a stacked ``PreparedOperand`` (leading
    stack dim on every leaf — a registered pytree, so vmap maps it directly);
    pass a ``matmul2d(a2, b2_or_prep)`` that accepts the corresponding slice.
    """
    a = jnp.asarray(a)
    b_vals = b.values if isinstance(b, PreparedOperand) else jnp.asarray(b)
    if a.ndim != 3 or b_vals.ndim != 3 or b_vals.shape[0] != a.shape[0]:
        raise ValueError(f"grouped_matmul wants (G,M,K) x (G,K,N), got "
                         f"{a.shape} x {b_vals.shape}")
    if not isinstance(b, PreparedOperand):
        b = b_vals
    return jax.vmap(matmul2d)(a, b)
