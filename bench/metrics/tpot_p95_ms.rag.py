"""``tpot_p95_ms.chat``, read in the cells judged by ``ttft_p95_ms``: every
step there holds prefill rows, so one step time sets both the gap between
tokens and how long a prompt takes to its first token."""
import os

from bench.harness import spec

read = spec.load_module(os.path.join(os.path.dirname(__file__),
                                     "tpot_p95_ms.chat.py"),
                        "bench_metric_tpot_p95_ms_chat").read
