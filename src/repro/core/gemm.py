"""GEMM backend registry — the paper's technique as a first-class framework feature.

Every matmul in every model and app goes through **one** entry point,
`dot(a, b, policy, layer=...)`. The policy selects, per layer, which arithmetic
executes it:

* ``exact``         — float dot (bf16/f32); the production path for training and
                      the large-model dry-runs (the MXU *is* the exact PE array).
* ``mxu_int8``      — symmetric int8 quantize -> exact int8 systolic GEMM (Pallas
                      kernel on TPU, jnp fallback elsewhere) -> dequantize.
* ``approx_lut``    — int8 quantize -> approximate GEMM via the PE product table at
                      factor k (Pallas gather kernel / jnp fallback) -> dequantize.
* ``approx_oracle`` — int8 quantize -> full fused bit-level PE-chain oracle.
* ``approx_onehot`` — one-hot rewrite running the approximate GEMM on the exact MXU.
* ``approx_delta``  — exact int8 MXU matmul + rank-r error-correction matmul
                      (core/error_delta.py): bit-identical to ``approx_lut`` at the
                      default (exact) rank, but MXU-resident — the fast path for
                      activations that change every call.

``dot`` accepts raw floats (quantize -> integer GEMM -> dequantize), raw
integers (integer-in / int32-out), or a ``PreparedOperand`` on either side —
the paper's weight-stationary dataflow: the fixed operand's quantization and
backend precompute (delta factors, one-hot tables) are done **once** and every
call pays only for the moving operand. ``bind(params, policy)`` applies this
to a whole model parameter pytree, returning ``BoundParams`` that the model
stack accepts interchangeably with raw params — decode then runs fully
weight-stationary.

The per-layer policy generalizes the paper's hybrid BDCN (approximate early
blocks, exact later blocks) to arbitrary networks.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import emulate, lut, quant

BACKENDS = ("exact", "mxu_int8", "approx_lut", "approx_oracle", "approx_onehot",
            "approx_delta")
GUARDS = ("none", "detect", "recompute")     # GemmPolicy.guard modes


@dataclasses.dataclass(frozen=True)
class GemmPolicy:
    """Which backend executes each layer's matmuls.

    `backend` is the default; `overrides` maps layer-name prefixes to backends
    (longest prefix wins; the empty prefix matches every layer and acts as a
    default-override), mirroring the paper's hybrid early-approx/late-exact
    BDCN. `k` is the approximation factor for approximate backends.
    `delta_rank` / `delta_tol` tune the ``approx_delta`` correction rank
    (None = exact rank, bit-identical to ``approx_lut``; a tolerance trades
    correction FLOPs for a bounded per-product error on top of the paper's
    approximation).

    ``delta_adaptive`` auto-selects the correction *form* per layer on the
    weight-stationary path: when the weight-restricted rank r' of a prepared
    layer exceeds its output width, the rank-r' correction matmuls cost more
    than the per-element gather they replace (the ROADMAP DCT-k=6 regime),
    so ``resolve`` falls back to ``approx_lut`` for that layer — bit-
    identical output, strictly less work. `prepare_weights` supplies the
    (out_width, delta_rank) hints; without hints resolution is unchanged.
    On a TPU the fallback is off: the gather path has no TPU lowering.

    ``guard`` selects the ABFT fault-detection mode (core/abft.py):
    ``'none'`` (default, zero overhead), ``'detect'`` (checksum every GEMM,
    raise/record ``AbftFaultError`` on mismatch), or ``'recompute'``
    (re-execute a flagged tile once and re-check). Thresholds come from the
    approximation's own error bounds, so intended approximation error never
    false-positives.
    """
    backend: str = "exact"
    k: int = 4
    n_bits: int = 8
    acc_bits: int = 24
    overrides: Optional[Dict[str, str]] = None
    delta_rank: Optional[int] = None
    delta_tol: Optional[float] = None
    delta_adaptive: bool = False
    guard: str = "none"

    def resolve(self, layer: str = "", *, out_width: Optional[int] = None,
                delta_rank: Optional[int] = None) -> str:
        if self.overrides:
            best = None
            choice = self.backend
            for prefix, be in self.overrides.items():
                if layer.startswith(prefix) and (best is None
                                                 or len(prefix) > len(best)):
                    best, choice = prefix, be
        else:
            choice = self.backend
        if (choice == "approx_delta" and self.delta_adaptive
                and out_width is not None and delta_rank is not None
                and delta_rank > out_width
                and jax.default_backend() != "tpu"):
            return "approx_lut"
        return choice


EXACT = GemmPolicy(backend="exact")


def as_policy(policy=None, *, backend: str = "approx_lut",
              k: Optional[int] = None) -> GemmPolicy:
    """Coerce ``None`` / a backend name / a GemmPolicy into a GemmPolicy.

    Application entry points accept all three; ``k`` (when given) overrides
    the policy's approximation factor, so apps can sweep k under one policy.
    """
    if policy is None:
        policy = GemmPolicy(backend=backend)
    elif isinstance(policy, str):
        if policy not in BACKENDS:
            raise ValueError(f"unknown backend {policy!r}; one of {BACKENDS}")
        policy = GemmPolicy(backend=policy)
    elif not isinstance(policy, GemmPolicy):
        raise TypeError(f"policy must be None, a backend name or a GemmPolicy,"
                        f" got {type(policy).__name__}")
    if k is not None and policy.k != k:
        policy = dataclasses.replace(policy, k=k)
    if policy.guard not in GUARDS:
        raise ValueError(f"unknown guard {policy.guard!r}; "
                         "one of ('none', 'detect', 'recompute')")
    return policy


def _int_gemm(x_q, w_q, backend: str, policy: GemmPolicy):
    if backend == "mxu_int8":
        from repro.kernels import ops
        return ops.systolic_matmul(x_q, w_q)
    if backend == "approx_lut":
        from repro.kernels import ops
        return ops.approx_matmul(x_q, w_q, k=policy.k, n_bits=policy.n_bits,
                                 acc_bits=policy.acc_bits)
    if backend == "approx_oracle":
        return emulate.matmul_oracle(x_q, w_q, n_bits=policy.n_bits, k=policy.k,
                                     acc_bits=policy.acc_bits)
    if backend == "approx_onehot":
        t_b = lut.build_onehot_weights(w_q, n_bits=policy.n_bits, k=policy.k,
                                       acc_bits=policy.acc_bits)
        return lut.onehot_matmul(x_q, t_b, n_bits=policy.n_bits)
    if backend == "approx_delta":
        from repro.kernels import ops
        return ops.approx_delta_matmul(x_q, w_q, k=policy.k,
                                       n_bits=policy.n_bits,
                                       acc_bits=policy.acc_bits,
                                       rank=policy.delta_rank,
                                       tol=policy.delta_tol)
    raise ValueError(f"unknown integer backend {backend!r}")


def _guard_mm(mm2d, policy: GemmPolicy, backend: str, layer: str, prep=None):
    """Wrap a 2-D integer matmul closure with the ABFT output-checksum guard.

    The wrapped closure receives the actual 2-D operands (the batched-app
    shim hands them over flattened), so the checksum matvecs see exactly what
    the kernel saw. ``prep`` supplies the clean-weight checksum metadata when
    the fixed operand was prepared (``core.abft.AbftMeta``), which pins the
    expected-value matvec to the *bind-time* weights.
    """
    if policy.guard == "none":
        return mm2d
    from . import abft
    meta = getattr(prep, "abft", None) if prep is not None else None
    meta_side = prep.side if prep is not None else "right"

    def guarded(a, b):
        acc = mm2d(a, b)
        return abft.guard_int_matmul(
            acc, a, b, policy=policy, backend=backend, layer=layer,
            meta=meta, meta_side=meta_side,
            recompute_fn=lambda: mm2d(a, b))
    return guarded


def _check_prepared(prep, backend: str, policy: GemmPolicy, layer: str) -> None:
    mismatches = []
    # the adaptive form: prepare_weights may resolve an approx_delta layer to
    # the (bit-identical) gather path when its restricted rank exceeds the
    # output width — accept the lut-prepared operand under the delta policy.
    # Only at the exact rank: a truncated delta_rank/delta_tol policy has no
    # bit-identical gather counterpart, so there the mismatch stays fatal.
    adaptive_ok = (policy.delta_adaptive and backend == "approx_delta"
                   and prep.backend == "approx_lut"
                   and policy.delta_rank is None and policy.delta_tol is None)
    if prep.backend != backend and not adaptive_ok:
        mismatches.append(f"backend {prep.backend!r} != {backend!r}")
    if prep.k != policy.k:
        mismatches.append(f"k {prep.k} != {policy.k}")
    if (prep.n_bits, prep.acc_bits) != (policy.n_bits, policy.acc_bits):
        mismatches.append("n_bits/acc_bits differ")
    if (backend == "approx_delta" and not adaptive_ok
            and (prep.rank, prep.tol) != (policy.delta_rank,
                                          policy.delta_tol)):
        mismatches.append("delta_rank/delta_tol differ")
    if mismatches:
        raise ValueError(
            f"prepared operand is stale for layer {layer!r}: "
            + "; ".join(mismatches)
            + " — re-run prepare_weights under the current policy")


def _is_float(x) -> bool:
    dt = getattr(x, "dtype", None)
    if dt is None:
        return isinstance(x, float)
    return jnp.issubdtype(dt, jnp.floating)


def _dequant(acc, x_scale, w_scale):
    """acc * x_scale * w_scale with a pinned evaluation order.

    The two scales are combined in float32 *first*, then applied in a single
    multiply. Writing the chain as ``(acc * s_x) * s_w`` lets XLA's broadcast
    simplifier reassociate it differently depending on whether the weight
    scale is computed inline (unbound) or arrives as an input (bound), which
    breaks bit-parity between the two paths; this canonical form is stable.
    """
    scale = x_scale.astype(jnp.float32) * w_scale.astype(jnp.float32)
    return acc.astype(jnp.float32) * scale


def _round_to(out_f32, dtype):
    """Cast the f32 dequantized output to `dtype`, pinning the rounding.

    A plain ``astype`` to a narrow float emits a convert that XLA's
    excess-precision folding may collapse with a downstream widening convert
    — whether it fires depends on the surrounding graph, so bound and unbound
    programs could hand different bits to the next layer. ``reduce_precision``
    performs the same rounding but is never folded, making the handed-off
    value context-independent.
    """
    if dtype == jnp.float32 or not jnp.issubdtype(dtype, jnp.floating):
        return out_f32.astype(dtype)
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(out_f32, fi.nexp, fi.nmant).astype(dtype)


# ---------------------------------------------------------------------------
# The unified entry point
# ---------------------------------------------------------------------------

def dot(a, b, policy: GemmPolicy = EXACT, *, layer: str = "",
        grouped: bool = False) -> jnp.ndarray:
    """One GEMM entry point for the whole stack (models, apps, kernels).

    Operand forms (either side, at most one prepared):

    * **raw floats** — the model path: the 2-D right-hand weight is quantized
      per-output-channel, the moving activations per-row (one scale per
      token, so a token's bits never depend on what else shares its batch —
      the invariant the continuous-batching serve engine relies on for
      per-request determinism), the integer GEMM runs under the layer's
      backend, and the result is dequantized back to the activations' dtype.
      ``backend="exact"`` is a plain float matmul.
    * **raw integers** — the app path (previously ``execute``/``int_matmul``):
      integer-in / int32-out under the layer's backend, batched operands
      flattened onto the 2D kernels by ``kernels.ops.batched_app_matmul``.
    * **a ``PreparedOperand``** — the weight-stationary path: built by
      ``prepare_weights`` (or ``bind`` for a whole model), its position must
      match the side it was prepared for. A prepared operand carrying a
      dequantization ``scale`` (prepared from floats) makes the call float-in
      / float-out with only the *moving* operand quantized per call; without
      a scale the call is integer-in / int32-out.
    * **grouped** — pass ``grouped=True`` for ``(G, M, K) x (G, K, N)``
      pairs sharing a leading group dim (MoE expert stacks): per-group
      quantization/preparation via ``kernels.ops.grouped_matmul``. Explicit
      rather than inferred, because a batched activation against a stacked
      3-D weight is shape-indistinguishable whenever the batch equals the
      stack size — inference would silently compute per-slice GEMMs. A
      *stacked prepared* operand is unambiguous and dispatches on its own.
    """
    from repro.kernels import ops
    policy = as_policy(policy, backend="exact")
    backend = policy.resolve(layer)
    a_prep = isinstance(a, ops.PreparedOperand)
    b_prep = isinstance(b, ops.PreparedOperand)
    if a_prep and b_prep:
        raise ValueError("at most one operand may be prepared")
    if a_prep or b_prep:
        prep = a if a_prep else b
        want_side = "left" if a_prep else "right"
        if prep.side != want_side:
            raise ValueError(
                f"operand prepared for side {prep.side!r} passed as "
                f"the {want_side} operand")
        _check_prepared(prep, backend, policy, layer)
        x = b if a_prep else a
        if prep.scale is not None and not _is_float(x):
            raise ValueError(
                f"layer {layer!r}: operand prepared from float weights "
                "needs a float moving operand (got integer input)")
        if prep.scale is None and _is_float(x):
            raise ValueError(
                f"layer {layer!r}: operand prepared from integer weights "
                "used with float input — prepare from the float weights "
                "instead so a dequantization scale is attached")
        if policy.guard != "none":
            from . import abft
            abft.verify_tables(policy, prep.backend, layer=layer)
            abft.guard_weight_meta(prep, layer=layer, guard=policy.guard)
        if prep.values.ndim > 2:                    # stacked (grouped) prepare
            return _dot_grouped(x, prep, policy, layer)
        if prep.scale is not None:
            return _dot_float_prepared(x, prep, policy, layer)
        x = jnp.asarray(x, jnp.int32)
        if a_prep:
            mm = lambda _, bb: ops.prepared_matmul(bb, prep)  # noqa: E731
            mm = _guard_mm(mm, policy, prep.backend, layer, prep)
            return ops.batched_app_matmul(mm, prep.values, x)
        mm = lambda aa, _: ops.prepared_matmul(aa, prep)      # noqa: E731
        mm = _guard_mm(mm, policy, prep.backend, layer, prep)
        return ops.batched_app_matmul(mm, x, prep.values)

    a = jnp.asarray(a)
    b = jnp.asarray(b)
    float_mode = _is_float(a) or _is_float(b)
    if grouped and not (a.ndim == 3 and b.ndim == 3
                        and a.shape[0] == b.shape[0]):
        raise ValueError(f"grouped=True wants (G, M, K) x (G, K, N), got "
                         f"{a.shape} x {b.shape}")
    if policy.guard != "none" and backend not in ("exact",):
        from . import abft
        abft.verify_tables(policy, backend, layer=layer)
    if not float_mode:
        a = a.astype(jnp.int32)
        b = b.astype(jnp.int32)
        if backend == "exact":
            if grouped:
                return jnp.matmul(a, b)
            mm = _guard_mm(jnp.matmul, policy, "exact", layer)
            return ops.batched_app_matmul(mm, a, b)
        mm = lambda aa, bb: _int_gemm(aa, bb, backend, policy)    # noqa: E731
        mm = _guard_mm(mm, policy, backend, layer)
        if grouped:
            return ops.grouped_matmul(mm, a, b)
        return ops.batched_app_matmul(mm, a, b)

    if backend == "exact":
        out = jnp.matmul(a, b)
        if policy.guard != "none" and b.ndim == 2:
            from . import abft
            out = abft.guard_float_matmul(out, a, b, policy=policy,
                                          layer=layer)
        return out
    if grouped:
        return _dot_grouped(a, b, policy, layer)
    if b.ndim != 2:
        raise ValueError(
            f"layer {layer!r}: the float path needs a 2-D right-hand weight "
            f"(got {a.shape} x {b.shape}); use prepare_weights(side='left') "
            "for fixed left operands")
    lead = a.shape[:-1]
    k_dim = a.shape[-1]
    x2 = a.reshape(-1, k_dim)
    xq = quant.quantize(x2, n_bits=policy.n_bits, axis=-1)  # per-row (token)
    wq = quant.quantize(b, n_bits=policy.n_bits, axis=0)   # per-output-channel
    mm = _guard_mm(lambda aa, bb: _int_gemm(aa, bb, backend, policy),
                   policy, backend, layer)
    acc = mm(xq.values, wq.values)
    out = _dequant(acc, xq.scale, wq.scale)
    return _round_to(out.reshape(*lead, b.shape[-1]), a.dtype)


def _dot_float_prepared(x, prep, policy: GemmPolicy,
                        layer: str = "") -> jnp.ndarray:
    """Float-in/float-out against a float-prepared (scaled) fixed operand.

    Mirrors the unprepared float path bit-for-bit: the moving operand is
    quantized per-row (per-column when the fixed operand is on the left)
    exactly as there, the integer GEMM is the same backend kernel, and the
    dequantization multiplies the same two scales.
    """
    from repro.kernels import ops
    x = jnp.asarray(x)
    if prep.side == "right":
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        xq = quant.quantize(x2, n_bits=policy.n_bits, axis=-1)     # per-row
        mm = lambda aa, _: ops.prepared_matmul(aa, prep)           # noqa: E731
        mm = _guard_mm(mm, policy, prep.backend, layer, prep)
        acc = mm(xq.values, prep.values)
        out = _dequant(acc, xq.scale, prep.scale)          # (R, 1) x (1, N)
        return _round_to(out.reshape(*lead, prep.values.shape[-1]), x.dtype)
    # fixed left operand W (M, K) x moving (..., K, N)
    xq = quant.quantize(x, n_bits=policy.n_bits, axis=-2)          # per-column
    mm = lambda _, bb: ops.prepared_matmul(bb, prep)               # noqa: E731
    mm = _guard_mm(mm, policy, prep.backend, layer, prep)
    acc = ops.batched_app_matmul(mm, prep.values, xq.values)
    out = _dequant(acc, xq.scale, prep.scale)          # (M, 1) x (..., 1, N)
    return _round_to(out, x.dtype)


def _dot_grouped(x, w_or_prep, policy: GemmPolicy, layer: str) -> jnp.ndarray:
    """Grouped GEMM (MoE experts): per-group quantize/prepare, 2-D kernels."""
    from repro.kernels import ops
    x = jnp.asarray(x)
    if _is_float(x):
        def mm(x2, w2):
            if isinstance(w2, ops.PreparedOperand):
                return _dot_float_prepared(x2, w2, policy, layer)
            xq = quant.quantize(x2, n_bits=policy.n_bits, axis=-1)
            wq = quant.quantize(w2, n_bits=policy.n_bits, axis=0)
            backend = policy.resolve(layer)
            gm = _guard_mm(lambda aa, bb: _int_gemm(aa, bb, backend, policy),
                           policy, backend, layer)
            acc = gm(xq.values, wq.values)
            return _round_to(_dequant(acc, xq.scale, wq.scale), x2.dtype)
        return ops.grouped_matmul(mm, x, w_or_prep)
    x = x.astype(jnp.int32)
    if isinstance(w_or_prep, ops.PreparedOperand):
        def mm(x2, p2):
            gm = lambda aa, _: ops.prepared_matmul(aa, p2)         # noqa: E731
            gm = _guard_mm(gm, policy, p2.backend, layer, p2)
            return gm(x2, p2.values)
    else:
        backend = policy.resolve(layer)
        mm = _guard_mm(lambda x2, w2: _int_gemm(x2, w2, backend, policy),
                       policy, backend, layer)
    return ops.grouped_matmul(mm, x, w_or_prep)


# ---------------------------------------------------------------------------
# Weight preparation + bound parameter pytrees
# ---------------------------------------------------------------------------

def prepare_weights(w, policy: GemmPolicy, *, layer: str = "",
                    side: str = "right", restrict: bool = True):
    """Precompute the backend-specific factor for a fixed weight matrix.

    Returns a ``kernels.ops.PreparedOperand`` that ``dot`` accepts in place
    of the raw matrix. Integer weights prepare as-is (integer-in/int32-out
    calls); **float** weights are first quantized per-output-channel (the
    second-to-last axis for ``side="right"``, the last for ``side="left"`` —
    the output dimension either way) and the scale is attached, so ``dot``
    runs float-in/float-out quantizing only the moving activations per call.

    For ``approx_delta`` this builds the rank-r ``G_B`` (or ``F_A`` for
    ``side="left"``, e.g. the DCT matrix multiplying from the left) once; for
    ``approx_onehot`` the ``T_B`` table. Prepare once per (weights, policy,
    layer) and reuse across every call — or use ``bind`` for a whole model.

    ``w`` may carry extra *leading* stack dimensions (scan-over-layers
    params, MoE expert stacks); the whole stack is then quantized and
    prepared in one vectorized pass — a single gather over the stacked bit
    patterns instead of a host loop over slices. Stacked preparation
    requires ``restrict=False`` so every slice shares one rank and the
    prepared pytree can ride a ``lax.scan`` (see ``bind``).

    Under ``policy.delta_adaptive``, an ``approx_delta`` layer whose
    weight-restricted rank exceeds its output width is prepared for the
    (bit-identical) ``approx_lut`` gather path instead — the per-layer
    correction-form auto-selection (`GemmPolicy.resolve` hints). Adaptive
    selection needs the restricted rank, so it applies to the 2-D
    ``restrict=True`` path only (stacked/bound preparations share the
    generic rank and keep the delta form).
    """
    from repro.core import error_delta
    from repro.kernels import ops
    backend = policy.resolve(layer)
    scale = None
    if _is_float(w):
        if backend == "exact":
            raise ValueError(
                f"layer {layer!r} resolves to the exact float backend — "
                "nothing to prepare; pass the raw weights to dot()")
        axis = -2 if side == "right" else -1
        wq = quant.quantize(jnp.asarray(w), n_bits=policy.n_bits, axis=axis)
        w, scale = wq.values, wq.scale
    if (backend == "approx_delta" and policy.delta_adaptive and restrict
            and policy.delta_rank is None and policy.delta_tol is None
            and getattr(w, "ndim", 0) == 2):
        # adaptive only at the exact (default) rank, where the delta and
        # gather forms are bit-identical — a truncated delta_rank/delta_tol
        # correction is deliberately approximate and must not be silently
        # swapped for the exact gather path
        r_eff = error_delta.restricted_rank(
            w, side=side, n_bits=policy.n_bits, k=policy.k,
            acc_bits=policy.acc_bits)
        out_w = w.shape[-1] if side == "right" else w.shape[-2]
        backend = policy.resolve(layer, out_width=out_w, delta_rank=r_eff)
    prep = ops.prepare_operand(w, backend=backend, k=policy.k,
                               n_bits=policy.n_bits, acc_bits=policy.acc_bits,
                               side=side, rank=policy.delta_rank,
                               tol=policy.delta_tol, restrict=restrict)
    if scale is not None:
        prep = dataclasses.replace(prep, scale=scale)
    # clean-weight checksums for the ABFT guard — attached unconditionally
    # (cheap, and keeps the prepared pytree structure guard-independent, so
    # the prepared-weights cache and jitted consumers never fork on `guard`)
    from . import abft
    prep = dataclasses.replace(
        prep, abft=abft.meta_for(prep.values, abft.prep_derived(prep)))
    return prep


_PREPARED_CACHE: "OrderedDict[tuple, Any]" = OrderedDict()
_PREPARED_CACHE_MAX = 256


def prepare_weights_cached(w, policy: GemmPolicy, *, layer: str = "",
                           side: str = "right", restrict: bool = True):
    """``prepare_weights`` memoized by weight *value* and policy parameters.

    Callers hit this on genuinely fixed matrices (the DCT matrix, conv
    kernels, model weights under ``bind``) so repeated forwards — every k of
    a sweep, every benchmark rep, every re-bind — reuse the stationary
    precompute. Keys hold a 16-byte BLAKE2b digest of the weight bytes (not
    the bytes themselves, which would pin every weight matrix alive in the
    key); shape/dtype ride along so a digest collision across layouts cannot
    alias. Eviction is LRU: the least-recently-used entry is dropped when the
    cache is full, so a long sweep no longer dumps the whole working set.
    """
    w_np = np.ascontiguousarray(np.asarray(w))
    digest = hashlib.blake2b(w_np.tobytes(), digest_size=16).digest()
    key = (digest, w_np.shape, w_np.dtype.str, policy.resolve(layer),
           policy.k, policy.n_bits, policy.acc_bits, policy.delta_rank,
           policy.delta_tol, policy.delta_adaptive, side, restrict)
    hit = _PREPARED_CACHE.get(key)
    if hit is not None:
        _PREPARED_CACHE.move_to_end(key)
        return hit
    hit = prepare_weights(w_np, policy, layer=layer, side=side,
                          restrict=restrict)
    _PREPARED_CACHE[key] = hit
    while len(_PREPARED_CACHE) > _PREPARED_CACHE_MAX:
        _PREPARED_CACHE.popitem(last=False)
    return hit


class BoundParams(dict):
    """A model parameter pytree whose weight leaves are policy-prepared.

    Behaves exactly like the raw params dict (same keys, same indexing, a
    registered pytree) so models, step builders, and the serving/eval loops
    accept it interchangeably with raw params — but every 2-D weight leaf
    that ``bind`` recognized is a ``PreparedOperand``: quantized once,
    backend factors built once, zero per-call weight work on the decode path.
    """


# Registered *with keys* so path-based flattening yields DictKeys, exactly
# like a plain dict — `bind` derives layer names from key paths, and a
# keyless registration would make re-binding a BoundParams under a new
# policy silently skip every top-level leaf (the path would carry an opaque
# FlattenedIndexKey instead of the leaf's name).
jax.tree_util.register_pytree_with_keys(
    BoundParams,
    lambda bp: (tuple((jax.tree_util.DictKey(k), bp[k]) for k in sorted(bp)),
                tuple(sorted(bp))),
    lambda keys, ch: BoundParams(zip(keys, ch)))


# Path components that are pure structure (stacking containers); they are
# dropped when deriving a leaf's layer name so bind-time names match the
# `layer=` strings the model code passes to `dot`.
STRUCTURAL_KEYS = frozenset({
    "layers", "groups", "tail", "mlstm_blocks", "slstm_blocks", "shared_attn",
})

# Leaf names that are 2-D GEMM weights consumed through `dot` (everything
# else — embeddings gathered by index, router logits, conv filters, gate
# matrices, norms — stays raw).
BINDABLE_LEAVES = frozenset({
    "wq", "wk", "wv", "wo", "w1", "w2", "w3", "up", "down", "w_in", "out",
    "in_proj", "out_proj", "lm_head", "patch_proj",
})


def default_layer_name(path) -> Optional[str]:
    """Map a pytree key path to the `layer=` name its `dot` call site uses.

    Structural container keys are dropped; the rest join with ``/`` — e.g.
    ``("layers", "attn", "wq") -> "attn/wq"``, ``("shared_attn", "mlp",
    "w1") -> "mlp/w1"``, ``("layers", "moe", "w1") -> "moe/w1"``. Returns
    ``None`` for leaves that are not bindable GEMM weights.
    """
    keys = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
    if not keys or keys[-1] not in BINDABLE_LEAVES:
        return None
    return "/".join(k for k in keys if k not in STRUCTURAL_KEYS)


def _bind_leaf(w, policy: GemmPolicy, name: str, cached: bool):
    """Prepare one weight leaf; extra leading dims are per-layer/expert stacks."""
    prep_fn = prepare_weights_cached if cached else prepare_weights
    if w.ndim == 2:
        return prep_fn(w, policy, layer=name)
    # Stacked weights (scan-over-layers params, MoE expert stacks): one
    # vectorized quantize + one gather over the stacked bit patterns, with
    # the generic (unrestricted) factors so all slices share one rank/pytree
    # structure. lax.scan / indexed tree.map slice the stack back off at run
    # time.
    return prep_fn(w, policy, layer=name, restrict=False)


def bind(params, policy: GemmPolicy, *,
         layer_fn: Optional[Callable] = None,
         tie_lm_head: bool = True, cached: bool = True) -> Any:
    """Bind a model parameter pytree to a policy: weight-stationary serving.

    Walks ``params``, and for every float 2-D (or stacked 3-D/4-D) weight
    leaf whose derived layer name resolves to a non-exact backend, replaces
    it with a ``PreparedOperand`` — quantized per-output-channel and
    backend-prepared **once**. Leaves under exact layers, non-GEMM leaves
    (embeddings, norms, routers, conv filters) and already-prepared leaves
    pass through untouched, so ``bind`` is idempotent and the result is
    accepted anywhere raw params are (models, ``launch.steps`` step builders,
    ``launch.serve``, ``train.loop.evaluate``).

    ``layer_fn(path) -> Optional[str]`` overrides ``default_layer_name`` to
    customize the path -> layer-name mapping. ``cached=False`` skips the
    module-level prepared-weights cache — use it when binding *transient*
    params (e.g. mid-training eval of the current optimizer state): those
    weights never repeat, so caching them would only pin dead prepared
    tensors in device memory until LRU eviction. With ``tie_lm_head``
    (default),
    a model with tied embeddings (no ``lm_head`` leaf) gets a prepared
    ``lm_head`` entry built from ``embed.T`` when the ``"lm_head"`` layer
    resolves non-exact — the vocab projection is the single hottest decode
    GEMM, and the raw tied path would otherwise re-quantize the embedding
    table every step.
    """
    from repro.kernels import ops
    layer_fn = layer_fn or default_layer_name
    is_prep = lambda x: isinstance(x, ops.PreparedOperand)        # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten_with_path(params, is_leaf=is_prep)
    leaves = []
    for path, leaf in flat:
        name = None if is_prep(leaf) else layer_fn(path)
        if (name is None or not hasattr(leaf, "ndim") or leaf.ndim < 2
                or not _is_float(leaf) or policy.resolve(name) == "exact"):
            leaves.append(leaf)
            continue
        leaves.append(_bind_leaf(leaf, policy, name, cached))
    out = jax.tree_util.tree_unflatten(treedef, leaves)
    if isinstance(out, dict):
        out = BoundParams(out)
        if (tie_lm_head and "embed" in out and "lm_head" not in out
                and policy.resolve("lm_head") != "exact"
                and _is_float(out["embed"])):
            prep_fn = prepare_weights_cached if cached else prepare_weights
            out["lm_head"] = prep_fn(
                jnp.asarray(out["embed"]).T, policy, layer="lm_head")
    return out


