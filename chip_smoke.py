"""Smoke run of the serve engine and the paper's image apps on a TPU chip.

    python chip_smoke.py                # one chip: kernels, serve, apps
    python chip_smoke.py --four-chips   # four chips: sharded train steps only

One process drives the chip; nothing here starts a child process. Without a
TPU the script exits non-zero before any phase runs.

Kernels phase: the systolic and approx_delta Pallas kernels must match
their pure-jnp references bit for bit at a smollm-360m width.

Serve phase: smollm-360m at its published widths (random weights from
``--seed``) through `launch.engine.ServeEngine` with the paged KV pool and
prefix cache, under four GEMM paths: exact on the gather path, exact on the
Pallas paged-attention kernel, bound mxu_int8 (the Pallas systolic kernel)
and bound approx_delta (the paper's approximate PE, weight-stationary).
Every request must finish with all of its tokens; the reference requests'
lockstep logits must be finite. Token agreement with solo lockstep, and of
the paged kernel with the gather path, is reported, not enforced.

Apps phase: 8x8 DCT compression at k=4 and Laplacian edge detection at k=2
through approx_delta at 512 px must reach the paper's Table VI PSNR less
``TOL_DB``.

Four-chip phase: three train steps of smollm-360m on a (data 2 x model 2)
mesh, compared with the same steps on a 1x1 mesh of one of those chips.

Timings printed here are smoke timings of one cold run, not benchmark
numbers. The last line of standard output is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Paper Table VI headline PSNRs and the slack tests/test_quality_regression.py
# allows them.
PAPER_DCT_K4_DB = 38.21
PAPER_EDGE_K2_DB = 30.45
TOL_DB = 0.5

# (label, backend, k, bind, paged_kernel). approx_delta runs at k=2: bound at
# k=4 its rank-7 stationary factors take ~41.6 B per weight, ~15 GB for
# smollm-360m, which does not fit one 16 GB chip; rank 2 at k=2 takes ~7.4 GB.
SERVE_PATHS = (
    ("exact/gather", "exact", 4, False, None),
    ("exact/paged-kernel", "exact", 4, False, 1),
    ("mxu_int8/bound", "mxu_int8", 4, True, None),
    ("approx_delta/bound", "approx_delta", 2, True, None),
)
# 128..1024-token prompts, all multiples of the prefill chunk, so the chunk
# step compiles at two widths only (16 and 1)
PROMPT_LENS = (128, 1024, 256, 896, 384, 768, 512, 640)

# Losses of the sharded and the one-chip train steps agree within this
# relative bound: activations are bf16, and the shards reduce in another order.
TRAIN_LOSS_RTOL = 5e-3


class CompileClock:
    """Sums XLA backend compile seconds reported through jax.monitoring."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def make_requests(cfg, prompt_lens, gen_len, *, shared_prefix, seed):
    """Greedy requests with seeded random prompts. From rid 2 on, prompts
    open with one shared ``shared_prefix``-token system prompt, and arrivals
    are staggered so that later requests can hit its cached blocks."""
    from repro.launch import engine
    rng = np.random.default_rng(seed)
    system = rng.integers(0, cfg.vocab_size, shared_prefix)
    reqs = []
    for rid, n in enumerate(prompt_lens):
        prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        if rid >= 2:
            prompt[:shared_prefix] = system
        reqs.append(engine.Request(rid=rid, prompt=prompt,
                                   max_new_tokens=gen_len, arrival=8 * rid))
    return reqs


def serve_phase(cfg, *, prompt_lens=PROMPT_LENS, gen_len=32, slots=8,
                block_size=16, prefill_chunk=16, shared_prefix=128, n_ref=2,
                seed=0, paths=SERVE_PATHS, log=print):
    """Run every serve path on the same requests; raise on any failed check.

    Returns {label: {"streams": {rid: tokens}, "agree": [...]}}."""
    from repro.core import gemm
    from repro.launch import engine, serve
    from repro.models import get_model

    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    max_len = max(prompt_lens) + gen_len
    out = {}
    for label, backend, k, bind, paged_kernel in paths:
        policy = gemm.GemmPolicy(backend=backend, k=k)
        # uncached: the process-wide prepared-weights cache would keep every
        # path's bound weights on the chip after the path is done
        p = model.bind_params(params, policy, cached=False) if bind else params
        reqs = make_requests(cfg, prompt_lens, gen_len,
                             shared_prefix=shared_prefix, seed=seed)
        eng = engine.ServeEngine(cfg, p, policy=policy, max_slots=slots,
                                 max_len=max_len, block_size=block_size,
                                 prefill_chunk=prefill_chunk,
                                 paged_kernel=paged_kernel, prefix_cache=True)
        finished = eng.run(reqs)
        streams = {}
        for r in reqs:
            f = finished.get(r.rid)
            if (f is None or f.finish_reason != "length"
                    or len(f.tokens) != gen_len):
                raise RuntimeError(f"{label}: request {r.rid} did not finish "
                                   f"with {gen_len} tokens: {f}")
            toks = np.asarray(f.tokens)
            if toks.min() < 0 or toks.max() >= cfg.vocab_size:
                raise RuntimeError(f"{label}: request {r.rid} sampled a "
                                   "token outside the vocabulary")
            streams[r.rid] = toks
        agree = []
        for r in reqs[:n_ref]:
            ref, logits = serve.lockstep_generate(
                cfg, model, p, r.prompt[None], gen_len, policy=policy,
                return_logits=True)
            if not np.isfinite(logits).all():
                raise RuntimeError(f"{label}: non-finite lockstep logits for "
                                   f"request {r.rid}")
            same = ref[0] == streams[r.rid]
            first = int(np.argmin(same)) if not same.all() else gen_len
            agree.append({"rid": r.rid, "prompt_len": len(r.prompt),
                          "agree": float(same.mean()),
                          "first_divergence": first})
        st = eng.stats
        out[label] = {"streams": streams, "agree": agree}
        log(f"serve {label}: {len(streams)}/{len(reqs)} requests finished, "
            f"{st['generated_tokens']} tokens, {st['decode_steps']} steps, "
            f"{st['prefix_hits']} prefix hits; lockstep agreement "
            + ", ".join(f"rid {a['rid']} (prompt {a['prompt_len']}): "
                        f"{a['agree']:.3f}, first divergence at "
                        f"{a['first_divergence']}" for a in agree))
        del eng, p
    if "exact/gather" in out and "exact/paged-kernel" in out:
        g, kern = out["exact/gather"]["streams"], out["exact/paged-kernel"][
            "streams"]
        same = np.stack([g[rid] == kern[rid] for rid in sorted(g)])
        log(f"serve paged-kernel vs gather streams: {same.mean():.3f} of "
            f"tokens agree, {int(same.all(axis=1).sum())}/{len(g)} streams "
            "identical")
    return out


def kernels_phase(*, m=64, k=960, n=1024, approx_ks=(2, 4), seed=0,
                  log=print):
    """The integer GEMM kernels against their pure-jnp references at a
    smollm-360m width: the systolic kernel and the approx_delta kernel at its
    exact rank must match bit for bit. Raise on any mismatch."""
    from repro.kernels import ops, ref
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
    b = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
    checks = [("systolic", ops.systolic_matmul(a, b),
               ref.systolic_matmul_ref(a, b))]
    for ak in approx_ks:
        checks.append((f"approx_delta k={ak}",
                       ops.approx_delta_matmul(a, b, k=ak),
                       ref.approx_matmul_ref(a, b, k=ak)))
    for name, got, want in checks:
        bad = int(np.sum(np.asarray(got) != np.asarray(want)))
        log(f"kernel {name} {m}x{k}x{n}: {bad} of {m * n} outputs differ "
            "from the reference")
        if bad:
            raise RuntimeError(f"kernel {name} differs from its reference in "
                               f"{bad} outputs")


def apps_phase(*, size=512, tol_db=TOL_DB, log=print):
    """DCT at k=4 and edge detection at k=2 through approx_delta; raise when
    a PSNR misses the paper's floor less ``tol_db``."""
    from repro.apps import dct, edge
    res = {}
    res["dct_k4_psnr"] = dct.run(size=size, ks=(0, 4),
                                 policy="approx_delta")[4]["psnr"]
    res["edge_k2_psnr"] = edge.run(size=size, ks=(2,),
                                   policy="approx_delta")[2]["psnr"]
    log(f"apps at {size} px (approx_delta): DCT k=4 PSNR "
        f"{res['dct_k4_psnr']} dB (floor {PAPER_DCT_K4_DB - tol_db}), edge "
        f"k=2 PSNR {res['edge_k2_psnr']} dB (floor "
        f"{PAPER_EDGE_K2_DB - tol_db})")
    if res["dct_k4_psnr"] < PAPER_DCT_K4_DB - tol_db:
        raise RuntimeError(f"DCT k=4 PSNR {res['dct_k4_psnr']} below floor")
    if res["edge_k2_psnr"] < PAPER_EDGE_K2_DB - tol_db:
        raise RuntimeError(f"edge k=2 PSNR {res['edge_k2_psnr']} below floor")
    return res


def train_losses(cfg, devices, mesh_shape, *, seq_len, batch, n_micro,
                 steps=3, seed=0):
    """Losses of ``steps`` sharded train steps (`launch.steps.assemble_train`)
    on a (data, model) mesh of ``devices``, from one seeded init."""
    from repro.configs.base import ShapeSpec
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.mesh import auto_mesh
    from repro.launch.steps import TrainHParams, assemble_train
    from repro.models import get_model
    from repro.optim import adamw

    mesh = auto_mesh(mesh_shape, ("data", "model"), devices)
    shape = ShapeSpec("smoke", "train", seq_len, batch)
    hp = TrainHParams(n_micro=n_micro, warmup_steps=1, total_steps=steps)
    step, _, in_sh, out_sh, hp = assemble_train(cfg, shape, mesh, hp)
    model = get_model(cfg)
    data = SyntheticLM(cfg, shape, DataConfig(n_micro=n_micro))
    losses = []
    with mesh:
        params = jax.jit(model.init_params, out_shardings=in_sh[0])(
            jax.random.PRNGKey(seed))
        opt = jax.jit(adamw.init, out_shardings=in_sh[1])(params)
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=(0, 1))
        for s in range(steps):
            batch_s = jax.device_put(data.batch(s), in_sh[2])
            params, opt, metrics = jitted(params, opt, batch_s)
            losses.append(float(metrics["loss"]))
    return losses


def four_chip_phase(cfg, *, seq_len=2048, batch=8, n_micro=4, steps=3,
                    log=print):
    devices = jax.devices()
    if len(devices) != 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found "
                           f"{len(devices)}")
    t0 = time.perf_counter()
    sharded = train_losses(cfg, devices, (2, 2), seq_len=seq_len,
                           batch=batch, n_micro=n_micro, steps=steps)
    t1 = time.perf_counter()
    single = train_losses(cfg, devices[:1], (1, 1), seq_len=seq_len,
                          batch=batch, n_micro=n_micro, steps=steps)
    t2 = time.perf_counter()
    log(f"train 2x2 mesh losses {sharded} ({t1 - t0:.1f} s with compile); "
        f"1x1 mesh losses {single} ({t2 - t1:.1f} s with compile)")
    if not np.allclose(sharded, single, rtol=TRAIN_LOSS_RTOL, atol=0):
        raise RuntimeError(f"sharded losses {sharded} differ from one-chip "
                           f"losses {single} beyond rtol {TRAIN_LOSS_RTOL}")
    if not np.isfinite(sharded).all():
        raise RuntimeError(f"non-finite losses {sharded}")
    return {"sharded": sharded, "single": single}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train phase on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro.configs import ARCHS
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    clock = CompileClock()
    cfg = ARCHS["smollm-360m"]
    print(f"smoke timings, not benchmark numbers; device {dev.device_kind} "
          f"x{len(jax.devices())}; compile cache {cache_dir}")
    phases = ([("four_chips", lambda: four_chip_phase(cfg))] if args.four_chips
              else [("kernels", lambda: kernels_phase(seed=args.seed)),
                    ("serve", lambda: serve_phase(cfg, seed=args.seed)),
                    ("apps", apps_phase)])
    for name, run in phases:
        t0, c0 = time.perf_counter(), clock.seconds
        run()
        print(f"smoke timing: phase {name} wall {time.perf_counter() - t0} s, "
              f"compile {clock.seconds - c0} s, peak_bytes_in_use "
              f"{peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
