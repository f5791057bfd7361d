"""A copy of the benchmark tree with one tiny cell, for CPU rehearsals.

The copy keeps the harness, the readers and the generator as they are and
adds a configuration, a traffic mix and a cell of CPU size, found by name
like every other; nothing in the real tree is edited."""
from __future__ import annotations

import json
import os
import shutil

from bench.harness import spec

TINY_SIZES = {"num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "intermediate_size": 128, "vocab_size": 256}
TINY_ENGINE = {"max_slots": 4, "max_len": 64, "block_size": 4,
               "prefill_chunk": 4, "prefix_cache": True}
TINY_TRAFFIC = {
    "generator": "requests",
    "params": {"prompt": {"median": 20, "sigma": 0.5, "min": 4, "max": 40},
               "answer": {"median": 6, "sigma": 0.4, "min": 3, "max": 10},
               "shared_share": 0.5, "n_shared": 2, "shared_len": 8,
               "min_user": 4, "block": 16}}
TINY_CELL = {"arrivals": {"kind": "poisson", "rate_per_s": 40.0},
             "preroll_s": 0.3, "trace_s": 0.4, "first_token_wait_s": 5.0,
             "check": {"max_logit_gap": 0.05, "min_top_share": 0.5,
                       "min_tokens": 20,
                       "max_requests": 6}}
# the same, served to a closed loop of one client per engine slot
TINY_CLOSED = dict(TINY_CELL, arrivals={"kind": "closed", "clients": 4,
                                        "rounds": 40})
CLOSED_NAME = "tiny.closed"


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_tree(tmp, *, backend: str = "approx_delta", k: int = 2,
              cell_name: str = "tiny.chat"):
    """(Benchmark over the copy, cell name); the copy also holds the closed
    loop cell ``CLOSED_NAME``."""
    root = os.path.join(str(tmp), "repo")
    bench_dir = os.path.join(root, "bench")
    shutil.copytree(spec.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    real = spec.Benchmark()
    base = real.config_file("smollm-360m.approx-delta-k2")
    conf = dict(base, **TINY_SIZES, name="tiny.model", tie_word_embeddings=False,
                engine=dict(TINY_ENGINE),
                gemm={"backend": backend, "k": k, "n_bits": 8, "bind": True})
    _write(os.path.join(bench_dir, "configs", "tiny.model.json"), conf)
    _write(os.path.join(bench_dir, "traffic", "tiny.json"), TINY_TRAFFIC)
    _write(os.path.join(bench_dir, "workloads", cell_name + ".json"),
           TINY_CELL)
    _write(os.path.join(bench_dir, "workloads", CLOSED_NAME + ".json"),
           TINY_CLOSED)
    cells = [cell_name, CLOSED_NAME]
    per_layer = []
    for m in real.spec["per_layer"]:
        if m["source"] == "device_trace" and m["name"] != "device_idle_share":
            continue
        per_layer.append(dict(m, workloads=cells))
    bm = dict(real.spec,
              configs=[{"name": "tiny.model", "source": "test",
                        "file": "bench/configs/tiny.model.json",
                        "reduced": list(TINY_SIZES), "why": "CPU size"}],
              workloads=[{"name": c, "config": "tiny.model",
                          "traffic": "tiny", "chips": 1, "why": "CPU size"}
                         for c in cells],
              end_to_end=[dict(m, workloads=cells) if "workloads" in m
                          else m for m in real.spec["end_to_end"]],
              per_layer=per_layer)
    _write(os.path.join(root, "BENCHMARK.json"), bm)
    return spec.Benchmark(root, bench_dir), cell_name
