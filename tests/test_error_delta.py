"""error_delta decomposition + approx_delta backend: bit-equality with the
gather path (lut.lut_matmul) across shapes/ranks, rank selection, padding."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic fallback, see _hypothesis_fallback.py
    from _hypothesis_fallback import given, settings, strategies as st

from repro.configs import ARCHS, reduced
from repro.core import error_delta, gemm, lut
from repro.core.emulate import product_table
from repro.kernels import ops
from repro.launch import engine as E
from repro.models import get_model


def _rand(shape, rng, lo=-128, hi=128):
    return jnp.asarray(rng.integers(lo, hi, shape), jnp.int32)


# --- decomposition ----------------------------------------------------------

@pytest.mark.parametrize("k", [0, 2, 4, 6])
def test_rank_for_exact_reproduces_table(k):
    fac = error_delta.delta_factors(8, k, True, 24)
    assert fac.rank == error_delta.rank_for_exact(8, k, True, 24)
    assert fac.exact, "residual must vanish at the exact rank"
    recon = np.round(fac.f.astype(np.float64) @ fac.g.astype(np.float64))
    t0 = product_table(8, 0, True, 24).astype(np.int64)
    tk = product_table(8, k, True, 24).astype(np.int64)
    np.testing.assert_array_equal(t0 + recon.astype(np.int64), tk)


def test_error_table_is_low_bit_periodic():
    # E depends only on the low k bits of each operand (the approximate cells
    # sit in columns < k) — the property that makes the rank small
    for k in (2, 4, 6):
        e = error_delta.error_table(8, k, True, 24)
        low = 1 << k
        np.testing.assert_array_equal(
            e, np.tile(e[:low, :low], (256 // low, 256 // low)))


def test_rank_selection():
    r_exact = error_delta.rank_for_exact(8, 6, True, 24)
    assert error_delta.rank_for_exact(8, 0, True, 24) == 0
    assert error_delta.rank_for_tol(0.0, 8, 6, True, 24) == r_exact
    e = error_delta.error_table(8, 6, True, 24)
    assert error_delta.rank_for_tol(float(np.abs(e).max()), 8, 6, True, 24) == 0
    # tolerance between the extremes buys a strictly smaller rank
    r_mid = error_delta.rank_for_tol(5.0, 8, 6, True, 24)
    assert 0 < r_mid < r_exact
    fac = error_delta.delta_factors(8, 6, True, 24, tol=5.0)
    assert fac.rank == r_mid and fac.max_err <= 5.0


def test_truncated_rank_residual_tracks_defect():
    fac = error_delta.delta_factors(8, 6, True, 24, rank=8)
    assert not fac.exact
    e = error_delta.error_table(8, 6, True, 24)
    recon = fac.f.astype(np.float64) @ fac.g.astype(np.float64)
    np.testing.assert_array_equal(fac.residual,
                                  e - np.round(recon).astype(np.int32))
    np.testing.assert_allclose(fac.defect, e - recon, atol=1e-3)


# --- reference + kernel bit-equality ---------------------------------------

SHAPES = [(8, 8, 8), (16, 24, 8), (33, 1, 5), (100, 70, 36), (1, 128, 1),
          (65, 129, 3)]


@pytest.mark.parametrize("m,kd,n", SHAPES)
@pytest.mark.parametrize("kf", [0, 3, 6])
def test_delta_ref_matches_lut(m, kd, n, kf):
    rng = np.random.default_rng(m * 5 + kd + n + kf)
    a, b = _rand((m, kd), rng), _rand((kd, n), rng)
    want = np.asarray(lut.lut_matmul(a, b, k=kf))
    out = np.asarray(error_delta.delta_matmul_ref(a, b, k=kf))
    np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("m,kd,n", SHAPES)
@pytest.mark.parametrize("kf", [0, 3, 6])
def test_delta_kernel_matches_lut(m, kd, n, kf):
    """Non-block-multiple shapes: padding + K-pad correction must stay exact."""
    rng = np.random.default_rng(m + kd * 7 + n + kf)
    a, b = _rand((m, kd), rng), _rand((kd, n), rng)
    want = np.asarray(lut.lut_matmul(a, b, k=kf))
    out = np.asarray(ops.approx_delta_matmul(a, b, k=kf))
    np.testing.assert_array_equal(out, want)
    if kf == 0:
        np.testing.assert_array_equal(out, np.asarray(a) @ np.asarray(b))


@pytest.mark.parametrize("kf", [4, 6])
def test_truncated_rank_with_residual_is_exact(kf):
    rng = np.random.default_rng(kf)
    a, b = _rand((40, 30), rng), _rand((30, 20), rng)
    want = np.asarray(lut.lut_matmul(a, b, k=kf))
    r = max(1, error_delta.rank_for_exact(8, kf, True, 24) // 2)
    out = np.asarray(ops.approx_delta_matmul(a, b, k=kf, rank=r,
                                             apply_residual=True))
    np.testing.assert_array_equal(out, want)
    ref = np.asarray(error_delta.delta_matmul_ref(a, b, k=kf, rank=r,
                                                  apply_residual=True))
    np.testing.assert_array_equal(ref, want)


def test_truncated_rank_error_bounded_by_tol():
    rng = np.random.default_rng(9)
    a, b = _rand((24, 16), rng), _rand((16, 24), rng)
    tol = 4.0
    want = np.asarray(lut.lut_matmul(a, b, k=6))
    out = np.asarray(ops.approx_delta_matmul(a, b, k=6, tol=tol,
                                             apply_residual=False))
    # per-product error <= tol, K products per output, plus <=0.5/block rounding
    assert np.abs(out - want).max() <= tol * 16 + 1


def test_unsigned_falls_back_to_reference():
    rng = np.random.default_rng(3)
    a = _rand((20, 12), rng, 0, 256)
    b = _rand((12, 10), rng, 0, 256)
    want = np.asarray(lut.lut_matmul(a, b, k=4, signed=False))
    out = np.asarray(ops.approx_delta_matmul(a, b, k=4, signed=False))
    np.testing.assert_array_equal(out, want)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 48), st.integers(1, 48), st.integers(1, 48),
       st.integers(0, 7))
def test_property_delta_matches_lut_any_shape(m, kd, n, kf):
    rng = np.random.default_rng(m * 311 + kd * 17 + n * 3 + kf)
    a, b = _rand((m, kd), rng), _rand((kd, n), rng)
    want = np.asarray(lut.lut_matmul(a, b, k=kf))
    np.testing.assert_array_equal(
        np.asarray(ops.approx_delta_matmul(a, b, k=kf)), want)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 40), st.integers(1, 40), st.integers(1, 40),
       st.integers(0, 7))
def test_property_approx_matmul_matches_lut_any_shape(m, kd, n, kf):
    """ops.approx_matmul (gather kernel) on non-block-multiple shapes, incl.
    the padded-K t00 correction, is bit-equal to the jnp gather path."""
    rng = np.random.default_rng(m * 131 + kd * 19 + n * 5 + kf)
    a, b = _rand((m, kd), rng), _rand((kd, n), rng)
    want = np.asarray(lut.lut_matmul(a, b, k=kf))
    np.testing.assert_array_equal(
        np.asarray(ops.approx_matmul(a, b, k=kf)), want)


# --- registry ---------------------------------------------------------------

def test_policy_delta_backend_bit_equals_lut_backend():
    rng = np.random.default_rng(11)
    xq = _rand((9, 33), rng)
    wq = _rand((33, 5), rng)
    pol_d = gemm.GemmPolicy(backend="approx_delta", k=4)
    pol_l = gemm.GemmPolicy(backend="approx_lut", k=4)
    np.testing.assert_array_equal(np.asarray(gemm.dot(xq, wq, pol_d)),
                                  np.asarray(gemm.dot(xq, wq, pol_l)))


def test_dot_delta_close_to_float():
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(size=(4, 24)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(24, 8)), jnp.float32)
    out = gemm.dot(x, w, gemm.GemmPolicy(backend="approx_delta", k=2))
    ref = x @ w
    rel = float(jnp.abs(out - ref).mean() / jnp.abs(ref).mean())
    assert rel < 0.08, rel


# --- the moving operand's factor lookup: selects on the low k bits ----------

def _prepared_slices(w, *, side, kf, signed, stacked):
    """(fixed operand, its PreparedDelta) pairs: one restricted preparation,
    or every slice of one stacked ``restrict=False`` preparation."""
    if not stacked:
        return [(w, error_delta.prepare_delta(w, side=side, k=kf,
                                              signed=signed))]
    prep = error_delta.prepare_delta(w, side=side, k=kf, signed=signed,
                                     restrict=False)
    return [(w[i], jax.tree.map(lambda leaf, i=i: leaf[i], prep))
            for i in range(w.shape[0])]


@pytest.mark.parametrize("stacked", (False, True), ids=("restricted", "stacked"))
@pytest.mark.parametrize("side", ("right", "left"))
@pytest.mark.parametrize("signed", (True, False), ids=("signed", "unsigned"))
@pytest.mark.parametrize("kf", (1, 2, 3, 4, 6))
def test_lookup_paths_match_lut(kf, signed, side, stacked):
    """Prepared, reference and Pallas-wrapper paths against the gather GEMM,
    bit for bit, on both sides of the select threshold and on odd shapes."""
    rng = np.random.default_rng(kf * 8 + signed * 4 + (side == "left") * 2
                                + stacked)
    lo, hi = (-128, 128) if signed else (0, 256)
    m, kd, n = 13, 37, 11
    stack = (2,) if stacked else ()
    fixed_shape = (kd, n) if side == "right" else (m, kd)
    w = _rand(stack + fixed_shape, rng, lo, hi)
    x = _rand((m, kd) if side == "right" else (kd, n), rng, lo, hi)
    for w2, prep in _prepared_slices(w, side=side, kf=kf, signed=signed,
                                     stacked=stacked):
        a, b = (x, w2) if side == "right" else (w2, x)
        want = np.asarray(lut.lut_matmul(a, b, k=kf, signed=signed))
        np.testing.assert_array_equal(
            np.asarray(error_delta.delta_matmul_prepared(x, prep)), want)
        np.testing.assert_array_equal(
            np.asarray(error_delta.delta_matmul_ref(a, b, k=kf,
                                                    signed=signed)), want)
        np.testing.assert_array_equal(
            np.asarray(ops.approx_delta_matmul(a, b, k=kf, signed=signed)),
            want)


@pytest.mark.parametrize("kf", (1, 2, 3, 4, 5, 6))
@pytest.mark.parametrize("signed", (True, False), ids=("signed", "unsigned"))
def test_select_lookup_equals_gather_bitwise(kf, signed):
    """The generic factors repeat every 2^k patterns, so the select chain
    returns the gather's float32 values bit for bit. Only the sign of a zero
    may differ (the SVD leaves -0.0 in some repeats of an all-zero pattern),
    which no sum and no rounding can see; adding +0.0 folds it away."""
    fac = error_delta.delta_factors(8, kf, signed, 24)
    rng = np.random.default_rng(kf)
    x_u = jnp.asarray(np.concatenate([np.arange(256),
                                      rng.integers(0, 256, 256)]).reshape(
                                          16, 32), jnp.int32)
    for tab in (jnp.asarray(fac.f.T), jnp.asarray(fac.g)):
        sel = np.asarray(error_delta.moving_factor(x_u, tab, kf))
        gat = np.asarray(error_delta.moving_factor(x_u, tab, None))
        assert sel.shape == gat.shape == (fac.rank, 16, 32)
        np.testing.assert_array_equal((sel + np.float32(0)).view(np.uint32),
                                      (gat + np.float32(0)).view(np.uint32))


def test_bound_lookup_lowers_without_gather_below_threshold():
    """A bound k=2 weight at reduced chat-like shapes lowers with no gather;
    a weight with more low-bit patterns than the threshold keeps it. The
    form, the tally and the engine's ``delta_forms`` say the same."""
    k_gather = next(kf for kf in range(1, 9)
                    if (1 << kf) > error_delta.SELECT_MAX_PATTERNS)
    rng = np.random.default_rng(14)
    x = _rand((32, 96), rng)
    for kf, form in ((2, "select"), (k_gather, "gather")):
        prep = error_delta.prepare_delta(_rand((96, 64), rng), k=kf,
                                         restrict=False)
        assert prep.form == form
        before = error_delta.FORM_SITES.copy()
        hlo = jax.jit(error_delta.delta_matmul_prepared).lower(
            x, prep).as_text()
        assert ("gather" in hlo) == (form == "gather"), form
        assert error_delta.FORM_SITES - before == {form: 1}

    cfg = dataclasses.replace(reduced(ARCHS["smollm-360m"]),
                              name="delta-forms-probe")
    model = get_model(cfg)
    pol = gemm.GemmPolicy(backend="approx_delta", k=2)
    params = model.bind_params(model.init_params(jax.random.PRNGKey(0)), pol)
    eng = E.ServeEngine(cfg, params, policy=pol, max_slots=2, max_len=16,
                        block_size=4, prefill_chunk=4)
    before = eng.stats["delta_forms"]
    eng.run([E.Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                       max_new_tokens=3)])
    after = eng.stats["delta_forms"]
    traced = {f: after.get(f, 0) - before.get(f, 0) for f in after}
    assert traced.get("select", 0) > 0 and not traced.get("gather"), traced
