"""Time one bound ``approx_delta`` GEMM with each lookup of the moving
operand's correction factor: the select chain on its low k bits and the
gather over the whole 256-entry table (``error_delta.moving_factor``).

Run on a TPU:  PYTHONPATH=src python -m benchmarks.delta_lookup [--out FILE]

Each case binds a random int8 weight as ``core.gemm.bind`` does
(``restrict=False``), warms up, then times ``--reps`` calls of the jitted
GEMM, each ended by ``block_until_ready``, and prints one JSON line: shape,
k, rank, form, median and fastest milliseconds, and whether the two forms
gave the same int32 output. ``SELECT_MAX_PATTERNS`` is set from these
readings.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import error_delta

# (M, K, N) of smollm-360m's projections in a 32-slot, 16-token chunk step
SHAPES = ((512, 960, 960), (512, 960, 2560), (512, 2560, 960))


def _time(fn, reps: int):
    fn().block_until_ready()                      # compile and warm up
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn().block_until_ready()
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times), min(times)


def run(shapes=SHAPES, ks=(2, 4, 5, 6), reps: int = 30, seed: int = 0):
    rng = np.random.default_rng(seed)
    rows = []
    for m, kd, n in shapes:
        x = jnp.asarray(rng.integers(-128, 128, (m, kd)), jnp.int32)
        w = rng.integers(-128, 128, (kd, n))
        for k in ks:
            prep = error_delta.prepare_delta(w, k=k, restrict=False)
            outs = {}
            for form, bits in (("select", k), ("gather", None)):
                def call(bits=bits):
                    return error_delta._delta_prepared_impl(
                        x, prep.w_u, prep.w_s, prep.factor, prep.gather_tab,
                        jnp.zeros((1,), jnp.float32), side="right",
                        rank=prep.rank, n_bits=8, signed=True,
                        use_defect=False, bits=bits)
                med, best = _time(call, reps)
                outs[form] = np.asarray(call())
                rows.append({"m": m, "k_dim": kd, "n": n, "k": k,
                             "rank": prep.rank, "form": form,
                             "median_ms": med, "min_ms": best})
            same = bool(np.array_equal(outs["select"], outs["gather"]))
            for row in rows[-2:]:
                row["same_output"] = same
                print(json.dumps(row), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ks", type=int, nargs="+", default=[2, 4, 5, 6])
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--out", default=None, help="also write the rows here")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}), flush=True)
    rows = run(ks=args.ks, reps=args.reps)
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
