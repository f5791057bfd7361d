"""Backend parity for the image apps: every approximate backend reproduces the
legacy numpy product-table path bit-for-bit.

Before this refactor the apps carried private table-lookup GEMMs
(``dct._gemm(fused=False)``, ``edge.conv_gemm``, ``bdcn.conv_layer``). Those
implementations are pinned *here* as ``_reference_*`` (using the cached
``emulate.product_table``) so the app layer can route through ``GemmPolicy``
while this tier proves the arithmetic is unchanged for ``approx_lut``,
``approx_onehot``, and ``approx_delta`` (at the exact rank); the fused-MAC
oracle path (``dct._gemm(fused=True)``) is pinned via ``emulate.pe_mac``.
"""
import numpy as np
import pytest

from repro.apps import bdcn, dct, edge, images
from repro.core import emulate, gemm, quant

PARITY_BACKENDS = ("approx_lut", "approx_onehot", "approx_delta")
SIZE = 48


# --- pinned legacy implementations ------------------------------------------

def _reference_gemm(a: np.ndarray, b: np.ndarray, k: int) -> np.ndarray:
    """The legacy apps/ table path: batched product-table lookups."""
    table = emulate.product_table(8, k, True, 24)
    return table[a[..., :, :, None] & 255, b[..., None, :, :] & 255].sum(axis=-2)


def _reference_fused_gemm(a: np.ndarray, b: np.ndarray, k: int) -> np.ndarray:
    """The legacy ``dct._gemm(fused=True)`` bit-level PE chain."""
    acc = np.zeros(a.shape[:-1] + (b.shape[-1],), np.int32)
    for kk in range(a.shape[-1]):
        acc = np.asarray(emulate.pe_mac(
            a[..., :, kk][..., :, None], b[..., kk, :][..., None, :], acc,
            n_bits=8, k=k, signed=True, acc_bits=24))
    return acc


def _reference_dct_forward(blocks: np.ndarray, k: int,
                           fused: bool = False) -> np.ndarray:
    g = _reference_fused_gemm if fused else _reference_gemm
    x = blocks.astype(np.int32) - 128
    t = np.broadcast_to(dct.T8, x.shape)
    s1 = np.clip(g(t, x, k) >> 7, -128, 127).astype(np.int32)
    return g(s1, np.broadcast_to(dct.T8.T.copy(), x.shape), k)


def _reference_conv_gemm(img: np.ndarray, kernel: np.ndarray,
                         k: int) -> np.ndarray:
    h, w = img.shape
    cols = edge.im2col(img.astype(np.int32) - 128)
    kflat = kernel.reshape(-1, 1)
    table = emulate.product_table(8, k, True, 24)
    out = table[cols & 255, kflat[None, :, 0] & 255].sum(axis=1)
    return out.reshape(h - 2, w - 2)


def _reference_conv_layer(x: np.ndarray, w: np.ndarray, k: int,
                          exact: bool) -> np.ndarray:
    c_out = w.shape[0]
    _, h, wd = x.shape
    cols = bdcn._im2col_nchw(x)
    wmat = w.reshape(c_out, -1).T
    xq = quant.quantize(np.asarray(cols))
    wq = quant.quantize(np.asarray(wmat), axis=0)
    a = np.asarray(xq.values)
    b = np.asarray(wq.values)
    if exact:
        acc = a.astype(np.int64) @ b.astype(np.int64)
    else:
        table = emulate.product_table(8, k, True, 24).astype(np.int64)
        acc = np.zeros((a.shape[0], b.shape[1]), np.int64)
        for kk in range(a.shape[1]):
            acc += table[a[:, kk] & 255][:, b[kk, :] & 255]
    out = acc.astype(np.float64) * np.asarray(xq.scale) * np.asarray(wq.scale)
    out = np.maximum(out, 0.0)
    return out.T.reshape(c_out, h, wd).astype(np.float32)


def _reference_bdcn_forward(img: np.ndarray, ws, k: int,
                            n_approx_blocks: int = 2) -> np.ndarray:
    x = (img.astype(np.float32) - 128.0) / 128.0
    x = x[None]
    side_maps = []
    for li, w in enumerate(ws):
        exact = (li >= n_approx_blocks) or k == 0
        x = _reference_conv_layer(x, w, k, exact)
        side_maps.append(np.abs(x).mean(axis=0))
    fwd = np.zeros_like(side_maps[0])
    for m in side_maps:
        fwd = 0.5 * fwd + m
    bwd = np.zeros_like(side_maps[0])
    for m in reversed(side_maps):
        bwd = 0.5 * bwd + m
    fused = fwd + bwd
    fused = 255.0 * fused / max(fused.max(), 1e-9)
    return np.clip(fused, 0, 255)


# --- parity -----------------------------------------------------------------

@pytest.mark.parametrize("backend", PARITY_BACKENDS)
@pytest.mark.parametrize("k", [0, 2, 4, 6])
def test_dct_backend_parity(backend, k):
    blocks = images.to_blocks(images.test_image(SIZE, 0))
    want = _reference_dct_forward(blocks, k)
    got = dct.forward_dct_blocks(blocks, k, policy=backend)
    np.testing.assert_array_equal(got, want)


def test_dct_oracle_backend_pins_fused_path():
    blocks = images.to_blocks(images.test_image(SIZE, 0))
    want = _reference_dct_forward(blocks, 4, fused=True)
    got = dct.forward_dct_blocks(blocks, 4, policy="approx_oracle")
    np.testing.assert_array_equal(got, want)
    # the default policy is the paper's fused-MAC simulation
    np.testing.assert_array_equal(dct.forward_dct_blocks(blocks, 4), want)


@pytest.mark.parametrize("backend", PARITY_BACKENDS)
@pytest.mark.parametrize("k", [0, 3, 6])
def test_edge_backend_parity(backend, k):
    img = images.test_image(SIZE, 1)
    want = _reference_conv_gemm(img, edge.LAPLACIAN, k)
    got = edge.conv_gemm(img, edge.LAPLACIAN, k, policy=backend)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", PARITY_BACKENDS)
def test_edge_backend_parity_laplacian8(backend):
    img = images.test_image(SIZE, 2)
    want = _reference_conv_gemm(img, edge.LAPLACIAN8, 4)
    got = edge.conv_gemm(img, edge.LAPLACIAN8, 4, policy=backend)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", PARITY_BACKENDS)
@pytest.mark.parametrize("k", [0, 4])
def test_bdcn_backend_parity(backend, k):
    img = images.test_image(SIZE, 0)
    ws = bdcn.make_weights([6, 8, 8], 0)
    want = _reference_bdcn_forward(img, ws, k)
    got = bdcn.bdcn_forward(img, ws, k, policy=backend)
    np.testing.assert_array_equal(got, want)


def test_bdcn_hybrid_policy_overrides_match_legacy_split():
    """n_approx_blocks maps onto per-layer GemmPolicy overrides."""
    img = images.test_image(SIZE, 3)
    ws = bdcn.make_weights([6, 8, 8, 8], 1)
    for n_approx in (1, 3):
        want = _reference_bdcn_forward(img, ws, 6, n_approx_blocks=n_approx)
        got = bdcn.bdcn_forward(img, ws, 6, n_approx_blocks=n_approx)
        np.testing.assert_array_equal(got, want)
    pol = bdcn.hybrid_policy(6, n_approx_blocks=1, n_blocks=4)
    assert pol.resolve(bdcn.layer_name(0)) == "approx_delta"
    assert pol.resolve(bdcn.layer_name(3)) == "exact"


@pytest.mark.parametrize("k", [2, 6])
def test_run_dicts_identical_across_table_backends(k):
    """End-to-end run() metrics agree bit-for-bit between the gather path and
    the MXU-resident delta path."""
    lut_res = dct.run(size=SIZE, ks=(k,), policy="approx_lut")
    delta_res = dct.run(size=SIZE, ks=(k,), policy="approx_delta")
    assert lut_res == delta_res
    lut_res = edge.run(size=SIZE, ks=(k,), policy="approx_lut")
    delta_res = edge.run(size=SIZE, ks=(k,), policy="approx_delta")
    assert lut_res == delta_res
