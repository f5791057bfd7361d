"""``device_idle_share``, read in the cells judged by ``ttft_p95_ms``: there
every engine step holds prefill rows, so the step sets how long a prompt
takes to its first token."""
import os

from bench.harness import spec

read = spec.load_module(
    os.path.join(os.path.dirname(__file__), "device_idle_share.py"),
    "bench_metric_device_idle_share").read
