"""The phases of chip_smoke.py, run on the CPU at a reduced size.

On the CPU every serve path keeps the engine == solo lockstep contract and
the paged kernel matches the gather path bit for bit, so the agreement the
script only reports on the chip is asserted here. The script's own ``main``
refuses any device that is not a TPU.
"""
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_serve_phase_reduced(smoke):
    cfg = reduced(ARCHS["smollm-360m"])
    lines = []
    out = smoke.serve_phase(cfg, prompt_lens=(32, 48, 16, 32), gen_len=4,
                            slots=4, block_size=8, prefill_chunk=16,
                            shared_prefix=16, log=lines.append)
    assert sorted(out) == sorted(p[0] for p in smoke.SERVE_PATHS)
    for label, res in out.items():
        assert len(res["streams"]) == 4
        assert [a["agree"] for a in res["agree"]] == [1.0, 1.0], label
    gather, kern = out["exact/gather"], out["exact/paged-kernel"]
    for rid, toks in gather["streams"].items():
        np.testing.assert_array_equal(toks, kern["streams"][rid])
    assert any("paged-kernel vs gather" in line for line in lines)


def test_serve_phase_fails_loudly_on_short_stream(smoke, monkeypatch):
    cfg = reduced(ARCHS["smollm-360m"])
    from repro.launch import engine
    real_run = engine.ServeEngine.run

    def drop_last(self, requests=(), **kw):
        fin = real_run(self, requests, **kw)
        fin[0].tokens = fin[0].tokens[:-1]
        return fin

    monkeypatch.setattr(engine.ServeEngine, "run", drop_last)
    with pytest.raises(RuntimeError, match="did not finish"):
        smoke.serve_phase(cfg, prompt_lens=(16, 16), gen_len=3, slots=2,
                          block_size=8, prefill_chunk=16, shared_prefix=8,
                          paths=smoke.SERVE_PATHS[:1], log=lambda _: None)


def test_kernels_phase_small(smoke):
    lines = []
    smoke.kernels_phase(m=8, k=64, n=128, log=lines.append)
    assert len(lines) == 3 and all(" 0 of " in line for line in lines)


def test_apps_phase_tiny(smoke):
    res = smoke.apps_phase(size=64, log=lambda _: None)
    assert res["dct_k4_psnr"] >= smoke.PAPER_DCT_K4_DB - smoke.TOL_DB
    assert res["edge_k2_psnr"] >= smoke.PAPER_EDGE_K2_DB - smoke.TOL_DB


def test_train_losses_one_device(smoke):
    cfg = reduced(ARCHS["smollm-360m"])
    losses = smoke.train_losses(cfg, jax.devices()[:1], (1, 1), seq_len=16,
                                batch=4, n_micro=2, steps=2)
    assert len(losses) == 2 and np.isfinite(losses).all()


def test_four_chip_phase_needs_four_devices(smoke):
    with pytest.raises(RuntimeError, match="needs 4 devices"):
        smoke.four_chip_phase(reduced(ARCHS["smollm-360m"]), log=print)


def test_compile_cache_follows_env_then_checkout(monkeypatch, tmp_path):
    """The helper is checked without switching the cache on: tests never
    enable it."""
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", path)]
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_last_line_contract_shape(smoke, monkeypatch, capsys):
    """With the device check satisfied, main's last line is exactly the
    JSON object the chip harness reads."""
    class FakeTpu:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return None

    monkeypatch.setattr(smoke.jax, "devices", lambda: [FakeTpu()])
    monkeypatch.setattr(smoke, "kernels_phase", lambda seed: None)
    monkeypatch.setattr(smoke, "serve_phase", lambda cfg, seed: {})
    monkeypatch.setattr(smoke, "apps_phase", lambda: {})
    monkeypatch.setattr(compile_cache, "enable", lambda: "cache")
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
