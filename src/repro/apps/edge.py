"""Kernel-based edge detection through the approximate systolic GEMM (paper §V-B).

The Laplacian convolution is lowered to im2col GEMM — (H*W, 9) x (9, 1) — and
routed through ``GemmPolicy``; output quality is measured against the
exact-arithmetic output of the identical pipeline.

The convolution kernel is a fixed weight: it is prepared once per k
(``gemm.prepare_weights``) so the weight-stationary backends (``approx_delta``
rank-r factor, ``approx_onehot`` T_B) amortize their precompute across every
im2col row. The default ``approx_delta`` backend reproduces the paper's
product-table model (``approx_lut``) bit-for-bit and runs on the TPU, where
the table gather has no kernel.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from repro.core import errors, gemm
from . import images

LAPLACIAN = np.array([[0, 1, 0], [1, -4, 1], [0, 1, 0]], dtype=np.int32)
LAPLACIAN8 = np.array([[1, 1, 1], [1, -8, 1], [1, 1, 1]], dtype=np.int32)

DEFAULT_BACKEND = "approx_delta"


def im2col(img: np.ndarray, kh: int = 3, kw: int = 3) -> np.ndarray:
    from numpy.lib.stride_tricks import sliding_window_view
    v = sliding_window_view(img, (kh, kw))           # (H-2, W-2, 3, 3)
    return v.reshape(-1, kh * kw)


def conv_gemm(img: np.ndarray, kernel: np.ndarray, k: int,
              policy=None) -> np.ndarray:
    """Approximate-GEMM convolution under the policy. img uint8 -> int32
    response map."""
    pol = gemm.as_policy(policy, backend=DEFAULT_BACKEND, k=k)
    h, w = img.shape
    cols = im2col(img.astype(np.int32) - 128)        # center into int8 range
    kflat = kernel.reshape(-1, 1)
    prep = gemm.prepare_weights_cached(kflat, pol, layer="edge.conv")
    out = np.asarray(gemm.dot(cols, prep, pol, layer="edge.conv"))
    return out[:, 0].reshape(h - 2, w - 2)


def edge_map(resp: np.ndarray) -> np.ndarray:
    mag = np.abs(resp).astype(np.float64)
    mag = 255.0 * mag / max(mag.max(), 1.0)
    return np.clip(mag, 0, 255)


def run(size: int = 256, ks=(2, 4, 6, 8), seed: int = 0,
        kernel: np.ndarray = LAPLACIAN, policy=None) -> Dict[int, Dict]:
    pol = gemm.as_policy(policy, backend=DEFAULT_BACKEND)
    img = images.test_image(size, seed)
    exact = edge_map(conv_gemm(img, kernel, 0, policy=pol))
    out = {}
    for k in ks:
        approx = edge_map(conv_gemm(img, kernel, k, policy=pol))
        out[k] = {"psnr": errors.psnr(exact, approx),
                  "ssim": errors.ssim(exact, approx)}
    return out
