"""Kernel- and CNN-based edge detection on the approximate PE (paper §V-B,
Table VI). The CNN (BDCN-style) uses the paper's hybrid policy: first two blocks
approximate, later blocks exact — expressed as GemmPolicy per-layer overrides.

Run:  PYTHONPATH=src python examples/edge_detection.py [--size 128]
          [--backend approx_lut|approx_delta|approx_onehot]

``approx_delta`` runs the convolution GEMMs MXU-resident with the
weight-stationary prepared kernel factors (bit-identical to ``approx_lut``,
up to ~70x faster on the 256px im2col GEMM — see BENCH_apps_backends.json).
"""
import argparse

from repro.apps import bdcn, edge


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=128)
    ap.add_argument("--bdcn-size", type=int, default=64)
    ap.add_argument("--backend", default=None,
                    help="GemmPolicy backend for the approximate GEMMs "
                         "(default approx_delta, bit-identical to the "
                         "paper's table model approx_lut)")
    args = ap.parse_args()
    paper_edge = {2: (30.45, 0.910), 4: (20.51, 0.894), 6: (12.76, 0.678),
                  8: (11.41, 0.651)}
    paper_bdcn = {2: (75.98, 1.0), 4: (68.55, 1.0), 6: (51.52, 0.999),
                  8: (34.60, 0.995)}
    be = args.backend or edge.DEFAULT_BACKEND
    print(f"Laplacian-kernel edge detection (backend {be}, approx vs exact):")
    for k, v in edge.run(size=args.size, policy=args.backend).items():
        pp, ps = paper_edge[k]
        print(f"  k={k}: PSNR {v['psnr']:6.2f} dB (paper {pp:5.2f})   "
              f"SSIM {v['ssim']:.3f} (paper {ps:.3f})")
    print("BDCN-style CNN edge detection (hybrid approx, first 2 blocks):")
    for k, v in bdcn.run(size=args.bdcn_size, policy=args.backend).items():
        pp, ps = paper_bdcn[k]
        print(f"  k={k}: PSNR {v['psnr']:6.2f} dB (paper {pp:5.2f})   "
              f"SSIM {v['ssim']:.3f} (paper {ps:.3f})")
    print("-> CNN-based consistently beats kernel-based (paper's key claim)")


if __name__ == "__main__":
    main()
