"""``step_sync_ms``, read in the cells judged by ``ttft_p95_ms``: there
every engine step holds prefill rows, so the step sets how long a prompt
takes to its first token."""
import os

from bench.harness import spec

read = spec.load_module(
    os.path.join(os.path.dirname(__file__), "step_sync_ms.py"),
    "bench_metric_step_sync_ms").read
