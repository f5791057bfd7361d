"""CPU rehearsals of whole runs, at a tiny size: discovery of added files,
the refusal without a chip, a clean run that is correct, and runs with the
timed path broken (or in lower precision) that must come out not correct.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench.harness import spec
from bench.tests import tiny

SEED = 99


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def cpu_devices(monkeypatch):
    """The harness's look for a chip is skipped; the rest runs on the CPU."""
    import jax
    from bench.harness import cell
    monkeypatch.setattr(cell, "devices_or_fail",
                        lambda chips: jax.devices()[:chips])


def run_tiny(tree, *, fault=None, conf=None, trace=False, name=None,
             reference_bits=None):
    """One whole run of the tiny cell; ``fault`` breaks the engine after its
    warm-up, ``conf`` serves the program under another configuration."""
    from bench.harness import cell
    bm, cell_name = tree
    t0 = time.perf_counter()
    opts = cell.Options(name or cell_name, SEED, 1.0, trace=trace)
    srv = cell.setup(opts, bm, conf=conf)
    if fault is not None:
        fault(srv.eng)
    return cell.run(opts, t0, srv=srv, reference_bits=reference_bits)


@pytest.mark.parametrize("name", [None, tiny.CLOSED_NAME],
                         ids=["open_loop", "closed_loop"])
def test_clean_run_is_correct(tree, name):
    out = run_tiny(tree, name=name)
    assert out["correct"] is True
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "readings", "check"]
    assert list(out["check"]) == ["max_logit_gap", "top_share",
                                  "tokens_compared"]
    assert set(out["metrics"]) == {"output_tok_per_s", "ttft_p95_ms",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] > 10 and out["failed"] == 0
    assert out["check"]["tokens_compared"]["value"] >= 20


def test_traced_run_reports_per_layer_metrics(tree):
    out = run_tiny(tree, trace=True)
    assert out["correct"] is True
    names = set(out["metrics"])
    assert {"queue_wait_p95_ms", "slot_occupancy", "engine_step_ms",
            "engine_step_ms.rag", "tpot_p95_ms.chat", "tpot_p95_ms.rag",
            "compile_s", "compiles_in_window", "prefix_hit_share"} <= names
    assert (out["metrics"]["engine_step_ms.rag"]
            == out["metrics"]["engine_step_ms"])
    assert "output_tok_per_s" not in names
    assert out["metrics"]["compiles_in_window"]["value"] == 0


def _alter_tokens(eng):
    """A token altered where it is produced: the step's sampled tokens."""
    step = eng._chunk

    def chunk(*args, **kw):
        tok, cache, state = step(*args, **kw)
        return (tok + 1) % eng.cfg.vocab_size, cache, state
    eng._chunk = chunk


def _state_unchanged(eng):
    """A step that returns its KV state unchanged."""
    step = eng._chunk

    def chunk(params, tokens, cache, *rest, **kw):
        tok, _, state = step(params, tokens, cache, *rest, **kw)
        return tok, cache, state
    eng._chunk = chunk


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(tree, fault):
    out = run_tiny(tree, fault=fault)
    assert out["correct"] is False
    assert (out["check"]["max_logit_gap"]["value"]
            > out["check"]["max_logit_gap"]["limit"])


def test_control_in_lower_precision_is_not_correct(tree):
    """The control: the program's own 4-bit operand path, and the reference
    computed with 4-bit operands, both fail the 8-bit comparison."""
    from bench import control
    from bench.harness import cell, check
    bm, name = tree
    conf = bm.config_file("tiny.model")
    out = run_tiny(tree, conf=control.program_conf(conf, "bits4"),
                   reference_bits=4)
    assert out["correct"] is False
    gap = out["check"]["max_logit_gap"]
    assert gap["value"] > 3 * gap["limit"]
    assert out["readings"]["control_max_logit_gap"] > 3 * gap["limit"]
    mod = bm.builder(conf)
    ref = mod.reference(conf, SEED)
    qw4, _, arith4 = mod.reference(conf, SEED, n_bits=4)
    rng = np.random.default_rng(0)
    items = [cell.Finished(rng.integers(0, 256, 20).astype(np.int32),
                           rng.integers(0, 256, 8).astype(np.int32))
             for _ in range(3)]
    r = check.compare(ref, items, seq_len=64, n_read=10,
                      control={"qw": qw4, "arith": arith4})
    assert r["control_max_logit_gap"] > 3 * gap["limit"]


def test_default_precision_fault_reaches_the_correction_contraction():
    """control.py's plant turns the approx_delta correction's HIGHEST
    contraction into a DEFAULT one, and lifting it restores HIGHEST."""
    import jax
    import jax.numpy as jnp
    from bench import control
    from repro.core import error_delta
    rng = np.random.default_rng(0)
    w = rng.integers(-128, 128, (16, 8)).astype(np.int32)
    x = jnp.asarray(rng.integers(-128, 128, (4, 16)), jnp.int32)
    prep = error_delta.prepare_delta(w, side="right", n_bits=8, k=2)

    def text():
        return str(jax.make_jaxpr(
            lambda a: error_delta.delta_matmul_prepared(a, prep))(x))
    assert "HIGHEST" in text()
    control.default_precision(True)
    try:
        assert "HIGHEST" not in text()
        assert np.array_equal(           # one f32 pass on the CPU: the same
            error_delta.delta_matmul_prepared(x, prep),
            error_delta.delta_matmul_ref(x, jnp.asarray(w), k=2))
    finally:
        control.default_precision(False)
    assert "HIGHEST" in text()


def test_added_files_are_found_by_name(tmp_path):
    """A cell, a configuration, a traffic kind and a per-layer metric are
    added as new files plus BENCHMARK.json entries, with no file edited."""
    bm, name = tiny.make_tree(tmp_path)
    d = bm.dir
    shutil.copy(os.path.join(d, "configs", "tiny.model.json"),
                os.path.join(d, "configs", "tiny.other.json"))
    with open(os.path.join(d, "traffic", "burst.py"), "w") as f:
        f.write("from bench.harness import spec\n"
                "base = spec.load_module(spec.BENCH_DIR + "
                "'/traffic/requests.py', 'rq')\n"
                "def generate(params, **kw):\n"
                "    return base.generate(params, **kw)[::2]\n")
    with open(os.path.join(d, "traffic", "tiny.burst.json"), "w") as f:
        json.dump(dict(tiny.TINY_TRAFFIC, generator="burst"), f)
    with open(os.path.join(d, "workloads", "tiny.new.json"), "w") as f:
        json.dump(tiny.TINY_CELL, f)
    with open(os.path.join(d, "metrics", "steps_in_window.py"), "w") as f:
        f.write("def read(ctx):\n    return float(len(ctx.steps_in_window()))\n")
    path = os.path.join(bm.root, "BENCHMARK.json")
    with open(path) as f:
        b = json.load(f)
    b["configs"].append(dict(b["configs"][0], name="tiny.other",
                             file="bench/configs/tiny.other.json"))
    b["workloads"].append({"name": "tiny.new", "config": "tiny.other",
                           "traffic": "tiny.burst", "chips": 1, "why": "t"})
    b["per_layer"].append({"name": "steps_in_window", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "chunk step", "moves": "setup_s",
                           "workloads": ["tiny.new"]})
    with open(path, "w") as f:
        json.dump(b, f)
    bm2 = spec.Benchmark(bm.root, bm.dir)
    assert bm2.workload("tiny.new")["config"] == "tiny.other"
    assert bm2.config_file("tiny.other")["hidden_size"] == 64
    assert hasattr(bm2.builder(bm2.config_file("tiny.other")), "build")
    assert bm2.cell("tiny.new") == tiny.TINY_CELL
    gen = bm2.generator(bm2.traffic("tiny.burst")["generator"])
    from bench.harness import seeds
    reqs = gen.generate(tiny.TINY_TRAFFIC["params"], rng=seeds.rng(1, 2),
                        arrivals=tiny.TINY_CELL["arrivals"], horizon_s=5.0,
                        vocab=256, max_len=64)
    assert reqs and all(r.rid % 2 == 0 for r in reqs)
    assert [m["name"] for m in bm2.per_layer("tiny.new")] == [
        "steps_in_window"]
    assert "steps_in_window" not in [m["name"] for m in bm2.per_layer(name)]
    # metrics without a workloads key apply to every cell, the others only
    # to the cells they list
    assert [m["name"] for m in bm2.end_to_end("tiny.new")] == ["setup_s"]
    assert bm2.metric_reader("steps_in_window").read(
        type("C", (), {"steps_in_window": lambda self: [1, 2]})()) == 2.0


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "smollm-360m.delta.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_run_refuses_without_a_tpu():
    proc = _run_py(spec.ROOT)
    assert proc.returncode != 0 and _no_result(proc)
    assert "TPU" in proc.stderr


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path)
    assert proc.returncode != 0 and _no_result(proc)


def test_reference_pe_matches_the_program_tables():
    from bench.reference import pe
    from repro.core import emulate
    for k in (0, 2, 4):
        assert np.array_equal(pe.product_table(8, k),
                              emulate.product_table(8, k, True, 24))
    assert pe.error_tile(8, 2).shape == (4, 4)
