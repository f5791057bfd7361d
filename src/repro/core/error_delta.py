"""Exact-plus-error-delta decomposition of the approximate product table.

The paper's approximate PE differs from the exact PE only in the columns
``< k`` of the partial-product array, so the approximate product table is the
exact product plus a structured error term:

    T_k[a, b] = a * b + E_k[a, b],      E_k = product_table(k) - product_table(0)

Because the approximate cells occupy columns ``< k`` only (and for ``k <= N-1``
those columns hold PPC cells fed exclusively by operand bits ``a_j b_i`` with
``i + j < k``), ``E_k[a, b]`` depends only on the **low k bits** of each
operand: the (2^N, 2^N) table is a (2^k, 2^k) tile repeated over the grid.  Its
true rank is therefore at most 2^k and empirically far lower — for N=8 signed:
rank 2 at k=2, 7 at k=4, 21 at k=6, 62 at k=8.

An SVD of ``E`` gives factors ``f (span, r)`` and ``g (r, span)`` with
``E ≈ f @ g``.  At ``r = rank_for_exact(...)`` the float64 reconstruction error
is ~1e-12, so rounding recovers every integer entry exactly, and the
approximate GEMM becomes **two MXU matmuls** instead of O(M·N·K) VPU gathers:

    out = A_s @ B_s                       (exact int8 matmul — the exact PE array)
        + round( F_A @ G_B )              ((M, rK) x (rK, N) float32 correction)

with ``F_A[m, kk*r + j] = f[a_u[m, kk], j]`` and
``G_B[kk*r + j, n] = g[j, b_u[kk, n]]`` — per-element lookups into 256-entry
vectors, trivially VMEM-resident.  Rounding the correction **per K-block** (as
the fused Pallas kernel in ``kernels/delta_gemm.py`` does) keeps the result
bit-identical to the gather path for any K, because each block's true
correction is an integer and the float32 noise per block is ~1e-2 << 0.5.

For truncated ranks (``rank_for_tol``) two residual views are kept:
``residual = E - round(f @ g)`` (int32 — nonzero only where the rank-r
reconstruction rounds to the wrong integer, for sparsity introspection) and
``defect = E - f @ g`` (float32 — the exact reconstruction defect). Callers
restore bit-exactness at any rank by gathering ``defect`` and rounding **once**
over ``correction + defect`` (rounding the two parts separately does not
commute with the summation, so the integer residual alone cannot cancel the
truncation exactly).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .emulate import product_table

# A rank is "exact" when the float64 reconstruction error is below this guard:
# small enough that per-entry rounding is exact and that float32 block-wise
# accumulation (error ~1e-2 at K-block 512, measured) stays well below 0.5.
EXACT_RECON_EPS = 1e-6


class DeltaFactors(NamedTuple):
    """Rank-r factorization of the error table for one (n_bits, k, signed, acc_bits)."""
    n_bits: int
    k: int
    signed: bool
    acc_bits: int
    rank: int
    f: np.ndarray          # (span, rank) float32 — row factor, indexed by a's bit pattern
    g: np.ndarray          # (rank, span) float32 — column factor, indexed by b's bit pattern
    residual: np.ndarray   # (span, span) int32 — E - round(f @ g); all-zero at rank_for_exact
    defect: np.ndarray     # (span, span) float32 — E - f @ g; exact-cancellation table
    max_err: float         # max |f @ g - E| over the table (float64 reconstruction)

    @property
    def exact(self) -> bool:
        return not self.residual.any()


@functools.lru_cache(maxsize=32)
def error_table(n_bits: int = 8, k: int = 4, signed: bool = True,
                acc_bits: int = 24) -> np.ndarray:
    """(2^N, 2^N) int32 table E[a_u, b_u] = T_k[a_u, b_u] - a*b (zero for k=0)."""
    t_k = product_table(n_bits, k, signed, acc_bits).astype(np.int64)
    t_0 = product_table(n_bits, 0, signed, acc_bits).astype(np.int64)
    return (t_k - t_0).astype(np.int32)


@functools.lru_cache(maxsize=32)
def _svd(n_bits: int, k: int, signed: bool, acc_bits: int):
    e = error_table(n_bits, k, signed, acc_bits).astype(np.float64)
    return np.linalg.svd(e)


def _recon_err(n_bits: int, k: int, signed: bool, acc_bits: int, rank: int) -> float:
    e = error_table(n_bits, k, signed, acc_bits).astype(np.float64)
    if rank == 0:
        return float(np.abs(e).max()) if e.size else 0.0
    u, s, vt = _svd(n_bits, k, signed, acc_bits)
    recon = (u[:, :rank] * s[:rank]) @ vt[:rank]
    return float(np.abs(recon - e).max())


@functools.lru_cache(maxsize=64)
def rank_for_exact(n_bits: int = 8, k: int = 4, signed: bool = True,
                   acc_bits: int = 24) -> int:
    """Smallest r whose float64 rank-r reconstruction rounds to E exactly.

    Equals the numerical rank of E (the tiled low-bit structure keeps it far
    below 2^N): the singular spectrum drops to ~0 past the true rank, so the
    reconstruction error falls from O(1) to O(1e-12) in one step.
    """
    _, s, _ = _svd(n_bits, k, signed, acc_bits)
    for r in range(len(s) + 1):
        if _recon_err(n_bits, k, signed, acc_bits, r) <= EXACT_RECON_EPS:
            return r
    raise AssertionError("full-rank SVD failed to reconstruct the error table")


@functools.lru_cache(maxsize=64)
def rank_for_tol(tol: float, n_bits: int = 8, k: int = 4, signed: bool = True,
                 acc_bits: int = 24) -> int:
    """Smallest r with max-abs per-entry reconstruction error <= tol.

    ``tol`` bounds the *additional* per-product error on top of the paper's
    approximation; the exact residual table lets callers cancel it again.
    """
    if tol < 0:
        raise ValueError(f"tol must be >= 0, got {tol}")
    r_exact = rank_for_exact(n_bits, k, signed, acc_bits)
    for r in range(r_exact + 1):
        if _recon_err(n_bits, k, signed, acc_bits, r) <= tol:
            return r
    return r_exact


@functools.lru_cache(maxsize=32)
def delta_factors(n_bits: int = 8, k: int = 4, signed: bool = True,
                  acc_bits: int = 24, rank: Optional[int] = None,
                  tol: Optional[float] = None) -> DeltaFactors:
    """Factor the error table at the requested rank (default: exact rank).

    ``rank`` wins over ``tol``; with neither, ``rank_for_exact`` is used and
    the residual is all-zero (the backend is then bit-identical to the gather
    path). Results are cached per configuration — the SVD runs once per
    (n_bits, k, signed, acc_bits).
    """
    if rank is None:
        rank = (rank_for_exact(n_bits, k, signed, acc_bits) if tol is None
                else rank_for_tol(tol, n_bits, k, signed, acc_bits))
    span = 1 << n_bits
    e = error_table(n_bits, k, signed, acc_bits)
    rank = max(0, min(rank, span))
    if rank == 0:
        f = np.zeros((span, 0), np.float32)
        g = np.zeros((0, span), np.float32)
        recon = np.zeros((span, span), np.float64)
    else:
        u, s, vt = _svd(n_bits, k, signed, acc_bits)
        sq = np.sqrt(s[:rank])
        f = (u[:, :rank] * sq).astype(np.float32)
        g = (sq[:, None] * vt[:rank]).astype(np.float32)
        recon = f.astype(np.float64) @ g.astype(np.float64)
    residual = (e.astype(np.int64) - np.round(recon).astype(np.int64)).astype(np.int32)
    defect = (e.astype(np.float64) - recon).astype(np.float32)
    max_err = float(np.abs(recon - e).max()) if e.size else 0.0
    return DeltaFactors(n_bits, k, signed, acc_bits, rank, f, g, residual,
                        defect, max_err)


@functools.lru_cache(maxsize=32)
def factor_tables_jnp(n_bits: int = 8, k: int = 4, signed: bool = True,
                      acc_bits: int = 24, rank: Optional[int] = None,
                      tol: Optional[float] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Device-resident flattened (f, g) for the Pallas kernel, uploaded once.

    f is flattened row-major (span, r) -> f_flat[v * r + j]; g row-major
    (r, span) -> g_flat[j * span + v].  rank 0 yields (span,)-zeros dummies so
    the kernel signature stays uniform.
    """
    fac = delta_factors(n_bits, k, signed, acc_bits, rank=rank, tol=tol)
    span = 1 << n_bits
    # force eager creation even under an outer jit/scan trace: these are
    # compile-time constants and the lru_cache must never capture a tracer
    with jax.ensure_compile_time_eval():
        if fac.rank == 0:
            z = jnp.zeros((span,), jnp.float32)
            return z, z
        return (jnp.asarray(np.ascontiguousarray(fac.f).reshape(-1)),
                jnp.asarray(np.ascontiguousarray(fac.g).reshape(-1)))


@functools.lru_cache(maxsize=32)
def _device_factors(n_bits: int, k: int, signed: bool, acc_bits: int,
                    rank: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Device-resident (f, g, defect_flat) for the jnp paths, uploaded once."""
    fac = delta_factors(n_bits, k, signed, acc_bits, rank=rank)
    with jax.ensure_compile_time_eval():   # lru_cache must not capture tracers
        return (jnp.asarray(fac.f), jnp.asarray(fac.g),
                jnp.asarray(fac.defect.reshape(-1)))


def _correction(a_u: jnp.ndarray, b_u: jnp.ndarray, fac: DeltaFactors) -> jnp.ndarray:
    """Unrounded rank-r correction: (M, rK) x (rK, N) float32 matmul."""
    m, kd = a_u.shape
    n = b_u.shape[1]
    f_dev, g_dev, _ = _device_factors(fac.n_bits, fac.k, fac.signed,
                                      fac.acc_bits, fac.rank)
    f_a = jnp.take(f_dev, a_u, axis=0)                        # (M, K, r)
    g_b = jnp.take(g_dev, b_u, axis=1)                        # (r, K, N)
    return jnp.matmul(f_a.reshape(m, kd * fac.rank),
                      jnp.transpose(g_b, (1, 0, 2)).reshape(kd * fac.rank, n),
                      precision=jax.lax.Precision.HIGHEST)


def defect_gather_matmul(a_u: jnp.ndarray, b_u: jnp.ndarray,
                         fac: DeltaFactors) -> jnp.ndarray:
    """sum_kk defect[a,b] via the shared gather loop (cached device table)."""
    from . import lut
    span = 1 << fac.n_bits
    _, _, defect_flat = _device_factors(fac.n_bits, fac.k, fac.signed,
                                        fac.acc_bits, fac.rank)
    return lut.table_gather_matmul(a_u, b_u, defect_flat, span=span)


@dataclasses.dataclass(frozen=True)
class PreparedDelta:
    """Weight-stationary half of the delta decomposition for a fixed operand.

    For a fixed weight matrix the operand-dependent factor of the correction —
    ``G_B[kk, j, n] = g[j, b_u[kk, n]]`` when the weights sit on the right,
    ``F_A[m, kk, j] = f[a_u[m, kk], j]`` when they sit on the left (the DCT
    matrix multiplies from the left; the product table is not symmetric, so
    the operand order cannot be swapped) — is computed **once** and reused for
    every batch of activations: each call then costs one exact int8 matmul
    plus one rank-r float32 contraction and only the *moving* operand's
    gathers.

    Because ``E`` only sees the fixed operand through its low-k bit patterns,
    the factorization is further specialized to the ``d`` *distinct* patterns
    the weights actually reach (``_restricted_factors``): an SVD of the
    restricted table ``E[:, used]`` (or ``E[used, :]``) gives an exact rank
    ``r' <= min(r, d)`` — e.g. the 8x8 DCT matrix needs rank 10 instead of 21
    at k=6, the Laplacian kernel rank 2 — shrinking the per-call gather and
    contraction by the same factor. Restriction applies only at the exact
    rank; explicitly truncated ranks keep the generic factors so the
    ``delta_tol`` semantics (and the defect table that cancels truncation)
    stay identical to the unprepared path. ``prepare_delta(restrict=False)``
    forces the generic factors — ``core.gemm.bind`` uses this for *stacked*
    layer weights so every layer shares one rank and the prepared pytrees can
    ride a ``lax.scan``.

    Registered as a JAX pytree (arrays are children; ``side``/``rank``/the
    factorization spec are static aux data) so prepared operands can be jit
    arguments and ``lax.scan`` xs.
    """
    side: str              # "right": fixed B (K, N); "left": fixed A (M, K)
    rank: int              # effective (possibly weight-restricted) rank
    spec: Tuple            # (n_bits, k, signed, acc_bits, rank_req, tol_req)
    w_u: jnp.ndarray       # fixed operand's unsigned bit patterns, int32
    w_s: jnp.ndarray       # fixed operand's signed (or unsigned) values, int32
    gather_tab: jnp.ndarray  # moving-side factor, (r', span) float32
    factor: jnp.ndarray    # stationary factor: (K, r', N) right / (M, K, r') left

    @property
    def fac(self) -> DeltaFactors:
        n_bits, k, signed, acc_bits, rank_req, tol_req = self.spec
        return delta_factors(n_bits, k, signed, acc_bits, rank=rank_req,
                             tol=tol_req)


jax.tree_util.register_pytree_node(
    PreparedDelta,
    lambda p: ((p.w_u, p.w_s, p.gather_tab, p.factor),
               (p.side, p.rank, p.spec)),
    lambda aux, ch: PreparedDelta(aux[0], aux[1], aux[2], *ch))


def _signed_values(w_u: jnp.ndarray, n_bits: int, signed: bool) -> jnp.ndarray:
    half = (1 << n_bits) >> 1
    return (w_u ^ half) - half if signed else w_u


def _base_matmul(a_s: jnp.ndarray, b_s: jnp.ndarray, signed: bool) -> jnp.ndarray:
    """Exact integer base product. Signed int8 operands take the MXU int8 path
    (int32 accumulate); unsigned values don't fit int8 and use an int32 dot."""
    if signed:
        return jax.lax.dot_general(
            a_s.astype(jnp.int8), b_s.astype(jnp.int8), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    return jnp.matmul(a_s, b_s)


# Restricted SVDs stay cheap: above this many distinct patterns the generic
# factors are reused (the rank gain vanishes as d approaches the table rank).
RESTRICT_MAX_PATTERNS = 128


@functools.lru_cache(maxsize=256)
def _restricted_factors(n_bits: int, k: int, signed: bool, acc_bits: int,
                        axis: int, patterns: Tuple[int, ...]):
    """Exact-rank factors of E restricted to the fixed operand's patterns.

    ``axis=1`` restricts columns (fixed right operand indexes E by its b
    patterns), ``axis=0`` rows. Returns (f, g, rank) with f (span, r') /
    g (r', d) for axis=1 and f (d, r') / g (r', span) for axis=0 — the
    d-sized side is indexed by position in ``patterns``. The restricted
    reconstruction is rounding-exact by construction (r' <= d suffices)."""
    e = error_table(n_bits, k, signed, acc_bits).astype(np.float64)
    sub = e[:, list(patterns)] if axis == 1 else e[list(patterns), :]
    u, s, vt = np.linalg.svd(sub, full_matrices=False)
    rank = len(s)
    for r in range(len(s) + 1):
        recon = (u[:, :r] * s[:r]) @ vt[:r]
        if np.abs(recon - sub).max() <= EXACT_RECON_EPS:
            rank = r
            break
    sq = np.sqrt(s[:rank])
    f = (u[:, :rank] * sq).astype(np.float32)
    g = (sq[:, None] * vt[:rank]).astype(np.float32)
    return f, g, rank


def _low_patterns(w_u: np.ndarray, n_bits: int, k: int) -> Tuple[int, ...]:
    low_mask = (1 << min(k, n_bits)) - 1
    return tuple(int(v) for v in np.unique(w_u & low_mask))


def _restrict_eligible(fac: DeltaFactors, patterns: Tuple[int, ...]) -> bool:
    """Whether the weight-restricted re-factorization applies — the single
    eligibility rule shared by `prepare_delta` and `restricted_rank`, so the
    adaptive correction-form decision can never diverge from what the
    preparation actually builds."""
    return (fac.rank > 0 and fac.exact
            and len(patterns) <= RESTRICT_MAX_PATTERNS)


def restricted_rank(w, *, side: str = "right", n_bits: int = 8, k: int = 4,
                    signed: bool = True, acc_bits: int = 24,
                    rank: Optional[int] = None,
                    tol: Optional[float] = None) -> int:
    """The correction rank ``prepare_delta(..., restrict=True)`` would use.

    Cheap relative to the full preparation (one cached SVD of the reached
    sub-table, no gathers over the weights) — `core.gemm.prepare_weights`
    uses it to decide, per layer, whether the rank-r' correction matmuls are
    even worth it: when r' exceeds the fixed operand's output width the
    per-element gather path (``approx_lut``) does strictly less work (the
    ROADMAP DCT-k=6 regime), and the two are bit-identical at exact rank.
    """
    fac = delta_factors(n_bits, k, signed, acc_bits, rank=rank, tol=tol)
    if fac.rank == 0:
        return 0
    w_np = np.asarray(jnp.asarray(w, jnp.int32)) & ((1 << n_bits) - 1)
    patterns = _low_patterns(w_np, n_bits, k)
    if not _restrict_eligible(fac, patterns):
        return fac.rank
    axis = 1 if side == "right" else 0
    return _restricted_factors(n_bits, k, signed, acc_bits, axis, patterns)[2]


def prepare_delta(w, *, side: str = "right", n_bits: int = 8, k: int = 4,
                  signed: bool = True, acc_bits: int = 24,
                  rank: Optional[int] = None,
                  tol: Optional[float] = None,
                  restrict: bool = True) -> PreparedDelta:
    """Precompute the fixed operand's correction factor (G_B or F_A) once.

    ``restrict=False`` skips the weight-restricted re-factorization and keeps
    the generic rank-r factors — the effective rank is then a function of the
    policy alone, so prepared operands for different weight matrices share one
    pytree structure (required when stacking per-layer preparations for a
    ``lax.scan``, as ``core.gemm.bind`` does). With ``restrict=False`` the
    fixed operand may also carry leading *stack* dimensions (scan-over-layers
    params, MoE expert stacks): the stationary factor for the whole stack is
    built by one fancy-index gather over the stacked bit patterns, and every
    array of the result keeps the stack dims in front.
    """
    if side not in ("right", "left"):
        raise ValueError(f"side must be 'right' or 'left', got {side!r}")
    fac = delta_factors(n_bits, k, signed, acc_bits, rank=rank, tol=tol)
    spec = (n_bits, k, signed, acc_bits, rank, tol)
    span = 1 << n_bits
    low_mask = (1 << min(k, n_bits)) - 1
    w_u = jnp.asarray(w, jnp.int32) & (span - 1)
    if w_u.ndim < 2:
        raise ValueError(f"prepared operand must be >= 2D, got {w_u.shape}")
    if w_u.ndim > 2 and restrict:
        raise ValueError(
            f"stacked preparation (shape {w_u.shape}) requires restrict=False")
    w_s = _signed_values(w_u, n_bits, signed)
    w_np = np.asarray(w_u)
    patterns = _low_patterns(w_np, n_bits, k) if (restrict and fac.rank) else ()
    restrict = restrict and _restrict_eligible(fac, patterns)
    if restrict:
        # E depends on the fixed operand only through its low-k bit patterns;
        # factor the reached sub-table at its own (smaller) exact rank.
        axis = 1 if side == "right" else 0
        f_np, g_np, r_eff = _restricted_factors(n_bits, k, signed, acc_bits,
                                                axis, patterns)
        pos = np.searchsorted(np.asarray(patterns), w_np & low_mask)
        if side == "right":
            gather_tab = jnp.asarray(f_np.T.copy())            # (r', span)
            g_b = g_np[:, pos]                                 # (r', K, N)
            factor = jnp.asarray(np.transpose(g_b, (1, 0, 2)).copy())
        else:
            gather_tab = jnp.asarray(g_np)                     # (r', span)
            factor = jnp.asarray(f_np[pos])                    # (M, K, r')
    else:
        r_eff = fac.rank
        if r_eff == 0:
            gather_tab = jnp.zeros((0, span), jnp.float32)
            shape = (w_np.shape[:-1] + (0,) + w_np.shape[-1:]
                     if side == "right" else w_np.shape + (0,))
            factor = jnp.zeros(shape, jnp.float32)
        elif side == "right":
            gather_tab = jnp.asarray(np.ascontiguousarray(fac.f.T))
            g_b = fac.g[:, w_np]                    # (r, *stack, K, N)
            factor = jnp.asarray(np.ascontiguousarray(
                np.moveaxis(g_b, 0, -2)))           # (*stack, K, r, N)
        else:
            gather_tab = jnp.asarray(fac.g)                    # (r, span)
            factor = jnp.asarray(fac.f[w_np])       # (*stack, M, K, r)
        stack = w_np.shape[:-2]
        if stack:
            # the moving-side table is weight-independent, but a stacked
            # preparation rides lax.scan — every leaf needs the stack dims
            gather_tab = jnp.broadcast_to(gather_tab, stack + gather_tab.shape)
    return PreparedDelta(side, r_eff, spec, w_u, w_s, gather_tab, factor)


@functools.partial(jax.jit, static_argnames=("side", "rank", "n_bits",
                                             "signed", "use_defect"))
def _delta_prepared_impl(x, w_u, w_s, factor, gather_tab, defect_flat, *,
                         side: str, rank: int, n_bits: int, signed: bool,
                         use_defect: bool):
    span = 1 << n_bits
    x_u = jnp.asarray(x, jnp.int32) & (span - 1)
    x_s = _signed_values(x_u, n_bits, signed)
    if side == "right":
        a_u, b_u = x_u, w_u
        base = _base_matmul(x_s, w_s, signed)
    else:
        a_u, b_u = w_u, x_u
        base = _base_matmul(w_s, x_s, signed)
    if rank:
        # transposed gather — r' row-contiguous sweeps over the flat moving
        # indices (far faster than per-index rank-r row gathers on CPU), then
        # one two-axis contraction against the precomputed stationary factor
        mov = jnp.take(gather_tab, x_u.reshape(-1), axis=1)
        if side == "right":
            m, kd = x_u.shape
            corr = jax.lax.dot_general(                 # (r,M,K) x (K,r,N)
                mov.reshape(rank, m, kd), factor, (((0, 2), (1, 0)), ((), ())),
                precision=jax.lax.Precision.HIGHEST)
        else:
            kd, n = x_u.shape
            corr = jax.lax.dot_general(                 # (M,K,r) x (r,K,N)
                factor, mov.reshape(rank, kd, n), (((1, 2), (1, 0)), ((), ())),
                precision=jax.lax.Precision.HIGHEST)
    else:
        corr = jnp.zeros(base.shape, jnp.float32)
    if use_defect:
        from . import lut
        corr = corr + lut.table_gather_matmul(a_u, b_u, defect_flat, span=span)
    return base + jnp.round(corr).astype(jnp.int32)


def delta_matmul_prepared(x, prep: PreparedDelta, *,
                          apply_residual: bool = True) -> jnp.ndarray:
    """Weight-stationary delta GEMM: only the moving operand ``x`` is gathered.

    ``x`` is the activations — (M, K) when the prepared weights are on the
    right, (K, N) when on the left. The whole call is one jit'd fusion of the
    exact int8 base matmul, the moving operand's rank-r' gathers, and the
    correction contraction against the precomputed stationary factor.
    Bit-identical to ``delta_matmul_ref`` / ``lut.lut_matmul`` at the exact
    rank and at any rank with ``apply_residual=True`` (single global rounding
    over correction + defect, exact while K·max|E|·eps_f32 stays far below
    0.5 — all app workloads)."""
    fac = prep.fac
    use_defect = apply_residual and not fac.exact
    if use_defect:
        _, _, defect_flat = _device_factors(fac.n_bits, fac.k, fac.signed,
                                            fac.acc_bits, fac.rank)
    else:
        defect_flat = jnp.zeros((1,), jnp.float32)
    return _delta_prepared_impl(x, prep.w_u, prep.w_s, prep.factor,
                                prep.gather_tab, defect_flat, side=prep.side,
                                rank=prep.rank, n_bits=fac.n_bits,
                                signed=fac.signed, use_defect=use_defect)


def delta_matmul_ref(a, b, *, k: int = 4, n_bits: int = 8, signed: bool = True,
                     acc_bits: int = 24, rank: Optional[int] = None,
                     tol: Optional[float] = None,
                     apply_residual: bool = True) -> jnp.ndarray:
    """Pure-jnp reference of the delta backend: base matmul + rank-r correction.

    Bit-identical to ``lut.lut_matmul`` at the exact rank, and at *any* rank
    when ``apply_residual=True`` (the defect gather restores exactness), for
    any (M, K) x (K, N).
    """
    fac = delta_factors(n_bits, k, signed, acc_bits, rank=rank, tol=tol)
    span = 1 << n_bits
    mask = span - 1
    half = span >> 1
    a_u = jnp.asarray(a, jnp.int32) & mask                    # (M, K) bit patterns
    b_u = jnp.asarray(b, jnp.int32) & mask                    # (K, N)
    if signed:
        a_s = (a_u ^ half) - half                             # sign-extend
        b_s = (b_u ^ half) - half
    else:
        a_s, b_s = a_u, b_u
    out = a_s @ b_s                                           # exact int32 base
    corr = _correction(a_u, b_u, fac) if fac.rank else jnp.zeros(out.shape,
                                                                 jnp.float32)
    if apply_residual and not fac.exact:
        corr = corr + defect_gather_matmul(a_u, b_u, fac)
    return out + jnp.round(corr).astype(jnp.int32)
