"""Readings that the correctness limits are set from (PERF.md gives them).

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \\
        [--faults bits4,exact,default_precision] [--program-seeds 4,5,...] \\
        [--seconds 10]

For each fault and each of ``--seeds`` the cell runs with the fault in the
program's place and is compared with the reference as the benchmark
compares it:

* ``bits4``, the control: the program's own lower operand width (4-bit
  operands in every GEMM). The same run also reads the gap of the token
  that the reference computed with 4-bit operands puts first.
* ``exact``: exact products served in place of the configuration's
  approximate PE (the policy at k=0).
* ``default_precision``: the ``approx_delta`` correction contraction at
  DEFAULT precision (one bf16 pass on a TPU) in place of HIGHEST.

For each of ``--program-seeds`` the cell runs as the benchmark runs it.
One process, one line per run; the benchmark's own runs never run these.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CONTROL_BITS = 4


class _Through:
    """A module stand-in: the given attributes, the rest from ``base``."""

    def __init__(self, base, **over):
        self._base = base
        self.__dict__.update(over)

    def __getattr__(self, name):
        return getattr(self._base, name)


def default_precision(on: bool) -> None:
    """Plant (or lift) the DEFAULT-precision correction contraction in the
    program's ``approx_delta`` path; on a change, drop every traced
    program so that the next trace sees it."""
    import jax
    from repro.core import error_delta
    if on == isinstance(error_delta.jax, _Through):
        return
    if on:
        def dot_general(*a, precision=None, **kw):
            return jax.lax.dot_general(*a, **kw)
        error_delta.jax = _Through(jax, lax=_Through(jax.lax,
                                                     dot_general=dot_general))
    else:
        error_delta.jax = jax
    jax.clear_caches()


def program_conf(conf, fault):
    conf = copy.deepcopy(conf)
    if fault == "bits4":
        conf["gemm"]["n_bits"] = CONTROL_BITS
    elif fault == "exact":
        conf["gemm"]["k"] = 0
    return conf


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--faults", default="bits4")
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench.harness import cell
    from bench.harness.spec import Benchmark

    def seeds(text):
        return [int(s) for s in text.split(",") if s]

    bench = Benchmark()
    conf = bench.config_file(bench.workload(args.workload)["config"])
    runs = ([(s, f) for f in args.faults.split(",") if f
             for s in seeds(args.seeds)]
            + [(s, "program") for s in seeds(args.program_seeds)])
    for seed, kind in runs:
        t0 = time.perf_counter()
        opts = cell.Options(args.workload, seed, args.seconds)
        default_precision(kind == "default_precision")
        srv = cell.setup(opts, bench, conf=program_conf(conf, kind))
        out = cell.run(opts, t0, srv=srv, reference_bits=(
            CONTROL_BITS if kind == "bits4" else None))
        print("reading " + json.dumps({
            "workload": args.workload, "seed": seed, "kind": kind,
            "correct": out["correct"],
            **{k: v["value"] for k, v in out["check"].items()},
            **out.get("readings", {}),
            "wall_s": time.perf_counter() - t0}), flush=True)
    default_precision(False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
