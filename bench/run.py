"""Run one cell of the on-chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and per-layer metrics are found by the
names in ``BENCHMARK.json`` (see ``bench/harness/spec.py``). The last line
of standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``)
and, last, ``check``: each number the correctness check compared, beside
its limit. The same numbers end standard error. Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench.harness import cell
    try:
        out = cell.run(cell.Options(args.workload, args.seed, args.seconds,
                                    trace=bool(args.trace)), T_PROCESS)
    except cell.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
