"""Benchmark harness — one function per paper table. Prints
``name,us_per_call,derived`` CSV rows (derived = the table's headline metric).

Run:  PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def _timeit(fn, *args, reps: int = 3, **kw):
    fn(*args, **kw)  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    return (time.perf_counter() - t0) / reps * 1e6, out


def table1_cells():
    """Table I: approximate cell truth tables, error rate, error probability."""
    from repro.core import pe
    us, cases = _timeit(pe.error_cases, pe.approx_ppc, nppc=False)
    num, den = pe.cell_error_probability(pe.approx_ppc, nppc=False)
    print(f"table1_ppc_error_rate,{us:.1f},{len(cases)}/16")
    print(f"table1_ppc_error_prob,{us:.1f},{num}/{den}")
    num_n, _ = pe.cell_error_probability(pe.approx_nppc, nppc=True)
    print(f"table1_nppc_error_prob,{us:.1f},{num_n}/{den}")


def table2_cells():
    """Table II: cell-level PDP + the paper's savings claims."""
    from repro.core import energy
    us, claims = _timeit(energy.cell_energy_claims)
    for k, v in claims.items():
        print(f"table2_{k},{us:.1f},{v:.3f}")


def table3_pe():
    """Table III: PE-level energy/PADP savings."""
    from repro.core import energy
    us, claims = _timeit(energy.pe_energy_claims)
    for k, v in claims.items():
        print(f"table3_{k},{us:.1f},{v:.3f}")


def table4_sa(fast: bool = False):
    """Table IV: SA-level PDP across sizes + GEMM energy extrapolation."""
    from repro.core import energy
    us, claims = _timeit(energy.sa_energy_claims)
    for k, v in claims.items():
        print(f"table4_{k},{us:.1f},{v:.3f}")
    for sa in (8, 16):
        e_ex = energy.gemm_energy_estimate(256, 256, 256, design="exact_ref6",
                                           sa_dim=sa)
        e_ap = energy.gemm_energy_estimate(256, 256, 256,
                                           design="proposed_approx", sa_dim=sa)
        print(f"table4_gemm256_sa{sa}_saving,0.0,"
              f"{1 - e_ap['energy_nJ'] / e_ex['energy_nJ']:.3f}")


def table5_errors(fast: bool = False):
    """Table V: NMED/MRED of the 8-bit PE vs k (ours vs paper)."""
    from repro.core import errors
    paper_signed = {2: (0.0001, 0.0037), 4: (0.0004, 0.0130),
                    6: (0.0022, 0.0481), 8: (0.0081, 0.2418)}
    ks = (2, 6) if fast else (2, 4, 6, 8)
    for k in ks:
        us, m = _timeit(errors.pe_error_metrics, 8, k, True, reps=1)
        pn, pm = paper_signed[k]
        print(f"table5_signed_k{k}_nmed,{us:.0f},{m['NMED']:.5f} (paper {pn})")
        print(f"table5_signed_k{k}_mred,{us:.0f},{m['MRED']:.5f} (paper {pm})")


def table6_apps(fast: bool = False):
    """Table VI: DCT / edge / BDCN application quality."""
    from repro.apps import bdcn, dct, edge
    size = 64 if fast else 128
    ks = (2, 8) if fast else (2, 4, 6, 8)
    us, res = _timeit(dct.run, size, ks, reps=1)
    for k, v in res.items():
        print(f"table6_dct_k{k},{us:.0f},psnr={v['psnr']:.2f}dB ssim={v['ssim']:.3f}")
    us, res = _timeit(edge.run, size, ks, reps=1)
    for k, v in res.items():
        print(f"table6_edge_k{k},{us:.0f},psnr={v['psnr']:.2f}dB ssim={v['ssim']:.3f}")
    us, res = _timeit(bdcn.run, 48 if fast else 64, ks, reps=1)
    for k, v in res.items():
        print(f"table6_bdcn_k{k},{us:.0f},psnr={v['psnr']:.2f}dB ssim={v['ssim']:.3f}")


def fig9_fig10_pareto(fast: bool = False):
    """Figs. 9/10: PDP vs NMED/MRED trade-off of the signed 8-bit PE vs k.
    PDP from the energy model (approx cells in the low-k columns, exact above),
    error from the exhaustive sweep."""
    from repro.core import energy, errors
    from repro.core.emulate import nppc_count, ppc_count
    ks = (2, 6) if fast else (2, 4, 5, 6, 8)
    n = 8
    exact_pdp = energy.pe_energy_from_cells("proposed_exact", n)
    for k in ks:
        frac = min(1.0, k / (2 * n - 1))     # fraction of columns approximated
        pdp = ((1 - frac) * energy.pe_energy_from_cells("proposed_exact", n)
               + frac * energy.pe_energy_from_cells("proposed_approx", n))
        m = errors.pe_error_metrics(n, k, signed=True)
        print(f"fig9_k{k},0.0,pdp={pdp:.0f}aJ({pdp/exact_pdp:.2f}x) "
              f"nmed={m['NMED']:.5f} mred={m['MRED']:.5f}")


def latency_wavefront():
    """Latency formula 3N-2 [11] from the cycle-accurate SA model."""
    from repro.core import systolic
    rng = np.random.default_rng(0)
    for n in (3, 4, 8):
        a = rng.integers(-8, 8, (n, n))
        b = rng.integers(-8, 8, (n, n))
        us, (out, cycles) = _timeit(systolic.simulate, a, b, reps=1)
        ok = np.array_equal(out, a @ b)
        print(f"latency_sa{n},{us:.0f},{cycles}cyc(3N-2={3*n-2}) exact={ok}")


def kernels_bench(fast: bool = False):
    """Pallas kernels (interpret mode on CPU): exact vs approx vs onehot."""
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.core import lut
    rng = np.random.default_rng(0)
    m = 128 if fast else 256
    a = jnp.asarray(rng.integers(-128, 128, (m, m)), jnp.int32)
    b = jnp.asarray(rng.integers(-128, 128, (m, m)), jnp.int32)
    us, _ = _timeit(lambda: np.asarray(ops.systolic_matmul(a, b)), reps=2)
    print(f"kernel_exact_{m}cube,{us:.0f},int8->int32")
    us, _ = _timeit(lambda: np.asarray(ops.approx_matmul(a, b, k=4)), reps=2)
    print(f"kernel_approx_lut_{m}cube,{us:.0f},k=4")
    tb = lut.build_onehot_weights(np.asarray(b), k=4)
    us, _ = _timeit(lambda: np.asarray(lut.onehot_matmul(a, tb)), reps=2)
    print(f"kernel_approx_onehot_{m}cube,{us:.0f},k=4 (MXU rewrite)")


def gemm_backends_bench(fast: bool = False):
    """Backend sweep: approx_lut vs approx_onehot vs approx_delta across
    M/N/K and k. Prints CSV rows and records the sweep (plus the delta-vs-lut
    speedup this PR's MXU-resident path must sustain) in
    BENCH_gemm_backends.json at the repo root."""
    import json
    import os
    import jax
    import jax.numpy as jnp
    from repro.core import error_delta, lut
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    sizes = [128, 256] if fast else [128, 256, 512]
    ks = (4,) if fast else (2, 4, 6)
    onehot_cap = 256   # the (K*256, N) f32 T_B at 512^3 is ~270 MB — skipped
    results = []
    for m in sizes:
        a = jnp.asarray(rng.integers(-128, 128, (m, m)), jnp.int32)
        b = jnp.asarray(rng.integers(-128, 128, (m, m)), jnp.int32)
        for kf in ks:
            reps = 1 if m >= 512 else 2
            us_lut, out_lut = _timeit(
                lambda: np.asarray(ops.approx_matmul(a, b, k=kf)), reps=reps)
            row = {"m": m, "n": m, "k_dim": m, "k": kf}
            rank = error_delta.rank_for_exact(8, kf, True, 24)
            us_delta, out_d = _timeit(
                lambda: np.asarray(ops.approx_delta_matmul(a, b, k=kf)),
                reps=reps)
            exact = bool(np.array_equal(out_d, out_lut))
            results.append({**row, "backend": "approx_lut",
                            "us_per_call": round(us_lut, 1)})
            results.append({**row, "backend": "approx_delta", "rank": rank,
                            "us_per_call": round(us_delta, 1),
                            "bit_exact_vs_lut": exact,
                            "speedup_vs_lut": round(us_lut / us_delta, 2)})
            print(f"bench_lut_{m}cube_k{kf},{us_lut:.0f},gather path")
            print(f"bench_delta_{m}cube_k{kf},{us_delta:.0f},rank={rank} "
                  f"exact={exact} speedup={us_lut / us_delta:.2f}x")
            if m <= onehot_cap:
                t_b = lut.build_onehot_weights(np.asarray(b), k=kf)
                us_oh, _ = _timeit(
                    lambda: np.asarray(lut.onehot_matmul(a, t_b)), reps=reps)
                results.append({**row, "backend": "approx_onehot",
                                "us_per_call": round(us_oh, 1),
                                "note": "T_B prebuilt (fixed weights)"})
                print(f"bench_onehot_{m}cube_k{kf},{us_oh:.0f},T_B prebuilt")
            else:
                print(f"bench_onehot_{m}cube_k{kf},0,skipped (T_B > "
                      f"{onehot_cap}^3 memory cap)")
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_gemm_backends.json")
    with open(path, "w") as f:
        json.dump({"device": jax.default_backend(),
                   "mode": "interpret" if jax.default_backend() != "tpu"
                   else "mosaic",
                   "fast": fast, "results": results}, f, indent=1)
    print(f"bench_backends_json,0,{os.path.normpath(path)}")


def apps_bench(fast: bool = False):
    """Application-workload backend sweep: DCT / edge / BDCN GEMMs routed
    through GemmPolicy, backend x k x image size. The weight-stationary
    ``approx_delta`` path (prepared G_B/F_A factors) must beat the
    ``approx_lut`` gather path; results recorded in BENCH_apps_backends.json
    with per-point bit-exactness vs the lut backend."""
    import json
    import os
    import jax
    from repro.apps import bdcn, dct, edge, images

    backends = ("approx_lut", "approx_onehot", "approx_delta")
    sizes = (64,) if fast else (128, 256)
    ks = (4,) if fast else (2, 4, 6)
    results = []

    def sweep(app, size, kf, fn):
        ref = None
        for be in backends:
            # sub-10ms workloads on a shared CPU need several reps to settle
            reps = 2 if size >= 256 else 6
            if be == "approx_onehot":
                reps = 1
            us, out = _timeit(fn, be, reps=reps)
            if be == "approx_lut":
                ref = (us, out)
            exact = bool(np.array_equal(out, ref[1]))
            row = {"app": app, "size": size, "k": kf, "backend": be,
                   "us_per_call": round(us, 1), "bit_exact_vs_lut": exact}
            if be != "approx_lut":
                row["speedup_vs_lut"] = round(ref[0] / us, 2)
            results.append(row)
            print(f"apps_{app}_{size}px_k{kf}_{be},{us:.0f},"
                  f"exact={exact}" + (f" speedup={ref[0] / us:.2f}x"
                                      if be != "approx_lut" else ""))

    for size in sizes:
        img = images.test_image(size, 0)
        blocks = images.to_blocks(img)
        for kf in ks:
            sweep("dct", size, kf,
                  lambda be, b=blocks, k=kf:
                  dct.forward_dct_blocks(b, k, policy=be))
            sweep("edge", size, kf,
                  lambda be, i=img, k=kf:
                  np.asarray(edge.conv_gemm(i, edge.LAPLACIAN, k, policy=be)))
    bdcn_size = 48 if fast else 64
    ws = bdcn.make_weights([8, 16, 16, 16], 0)
    img = images.test_image(bdcn_size, 0)
    for kf in ks:
        sweep("bdcn", bdcn_size, kf,
              lambda be, k=kf: bdcn.bdcn_forward(img, ws, k, policy=be))
    summary = {}
    for app in ("dct", "edge", "bdcn"):
        sp = [r["speedup_vs_lut"] for r in results
              if r["app"] == app and r["backend"] == "approx_delta"]
        if sp:
            summary[f"{app}_delta_geomean_speedup_vs_lut"] = round(
                float(np.exp(np.mean(np.log(sp)))), 2)
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_apps_backends.json")
    with open(path, "w") as f:
        json.dump({"device": jax.default_backend(),
                   "mode": "interpret" if jax.default_backend() != "tpu"
                   else "mosaic",
                   "fast": fast,
                   "note": "approx_delta runs weight-stationary (prepared "
                           "weight-restricted rank-r' factors); approx_onehot "
                           "prepares T_B where the weights sit on the right",
                   "summary": summary,
                   "results": results}, f, indent=1)
    for k, v in summary.items():
        print(f"bench_apps_{k},0,{v}x")
    print(f"bench_apps_json,0,{os.path.normpath(path)}")


def serve_bound_bench(fast: bool = False):
    """Decode throughput, bound (weight-stationary) vs unbound params.

    Builds the reduced smollm decode step under ``mxu_int8`` and
    ``approx_delta`` policies, measures tokens/s with raw params (weights
    quantized + factors rebuilt every step) vs ``gemm.bind``-bound params
    (all weight work done once), checks the two decode streams are
    bit-exact, and records the sweep in BENCH_serve_bound.json.
    """
    import json
    import os
    import time
    import jax
    import jax.numpy as jnp
    from repro.configs import ARCHS, reduced
    from repro.core import gemm
    from repro.models import get_model

    cfg = reduced(ARCHS["smollm-360m"])
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    b, pl = 2, 8
    gl = 4 if fast else 8
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, pl)), jnp.int32)
    results = []
    for backend in ("mxu_int8", "approx_delta"):
        pol = gemm.GemmPolicy(backend=backend, k=4)
        dec = jax.jit(lambda p, t, c, pos:
                      model.decode_step(p, t, c, pos, policy=pol))
        pre = jax.jit(lambda p, bt, c: model.prefill(p, bt, c, policy=pol))
        t0 = time.perf_counter()
        bound = model.bind_params(params, pol)
        bind_s = time.perf_counter() - t0
        row = {"backend": backend, "batch": b, "gen_len": gl,
               "bind_s": round(bind_s, 3)}
        streams = {}
        for name, p in (("unbound", params), ("bound", bound)):
            cache = model.init_cache(b, pl + gl + 1)
            logits, cache = pre(p, {"tokens": prompts}, cache)
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            # warmup decode (compile) — block so async dispatch of the warmup
            # (and prefill) can't bleed into the timed region
            jax.block_until_ready(dec(p, tok, cache, jnp.int32(pl)))
            toks = [np.asarray(tok)]
            t0 = time.perf_counter()
            for i in range(gl):
                logits, cache = dec(p, tok, cache, jnp.int32(pl + i))
                tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
                toks.append(np.asarray(tok))
            jax.block_until_ready(logits)
            dt = time.perf_counter() - t0
            streams[name] = (np.concatenate(toks, axis=1),
                             np.asarray(logits))
            row[f"{name}_us_per_tok"] = round(dt / (b * gl) * 1e6, 1)
            row[f"{name}_tok_per_s"] = round(b * gl / dt, 1)
        row["bit_exact"] = bool(
            np.array_equal(streams["unbound"][1], streams["bound"][1])
            and np.array_equal(streams["unbound"][0], streams["bound"][0]))
        row["speedup"] = round(row["unbound_us_per_tok"]
                               / row["bound_us_per_tok"], 2)
        results.append(row)
        print(f"serve_bound_{backend},{row['bound_us_per_tok']:.0f},"
              f"speedup={row['speedup']}x exact={row['bit_exact']} "
              f"bind={row['bind_s']}s")
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_serve_bound.json")
    with open(path, "w") as f:
        json.dump({"device": jax.default_backend(),
                   "mode": "interpret" if jax.default_backend() != "tpu"
                   else "mosaic",
                   "fast": fast, "arch": "smollm-360m (reduced)",
                   "note": "bound = gemm.bind(params, policy): weight "
                           "quantization + backend factors built once; "
                           "unbound re-derives them inside every decode step",
                   "results": results}, f, indent=1)
    print(f"bench_serve_bound_json,0,{os.path.normpath(path)}")


def serve_engine_bench(fast: bool = False):
    """Continuous-batching engine vs padded lockstep on a ragged Poisson trace.

    Replays one fixed ragged trace (heavy-tailed gen lengths, Poisson
    arrivals) through (a) the **padded lockstep loop** — the pre-engine
    serving semantics: one fixed (prompt_len, gen_len) = the trace maxima,
    requests grouped into arrival-order batches, every request padded to the
    slowest one; (b) a per-batch-padded lockstep variant (each batch padded
    only to its own maxima — a stronger baseline, recorded for reference);
    and (c) `launch.engine.ServeEngine` (paged + chunked prefill) with the
    same number of slots. Useful-token throughput (each request's own
    tokens / wall time) per backend x bind cell, plus the vectorized
    `gemm.bind` latency, recorded in BENCH_serve_engine.json.

    Two PR-5 cells ride along: **capacity** (max concurrent requests at one
    fixed KV budget, paged block pool vs contiguous per-slot regions) and
    **chunked_prefill** (useful tokens/s on a bursty arrival trace, chunked
    prefill vs the contiguous engine's one-request-per-dispatch prefill).
    The PR-9 **multi_step_n{4,8}** cells measure fused decode horizons
    (`ServeEngine(multi_step=n)`) against the per-step engine on a
    decode-heavy trace, recording syncs-per-token alongside throughput.
    The PR-10 **prefix_cache** / **prefix_capacity** cells measure prefix
    caching on a repeated shared-prefix trace: warm-over-cold useful tok/s
    and peak concurrency at a fixed block budget, with in-bench stream
    parity (cached == uncached, bit for bit).
    The scheduled CI job diffs this file against the committed baseline and
    fails on a >20% drop in the same-run relative metrics — engine-vs-lockstep speedup, concurrency ratio, chunked-prefill speedup, multi-step speedup, prefix-cache speedup and concurrency (benchmarks/compare.py).
    """
    import json
    import os
    import time
    import jax
    import jax.numpy as jnp
    from repro.configs import ARCHS, reduced
    from repro.core import gemm
    from repro.launch import engine as engine_mod
    from repro.launch.serve import lockstep_generate
    from repro.models import get_model

    cfg = reduced(ARCHS["smollm-360m"])
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    slots = 4
    n_req = 12 if fast else 16
    # heavy-tailed gen lengths: most requests are short, a few are long — the
    # regime where lockstep pads every request to the slowest one
    trace = engine_mod.make_poisson_trace(
        n_req, rate=4.0, vocab_size=cfg.vocab_size, prompt_lens=(4, 6),
        gen_lens=(6, 8, 10, 12, 56), seed=0)
    pl_max = max(len(r.prompt) for r in trace)
    gl_max = max(r.max_new_tokens for r in trace)
    max_len = pl_max + gl_max
    useful = sum(r.max_new_tokens for r in trace)
    grid = [("exact", False), ("mxu_int8", True), ("approx_delta", True)]
    if not fast:
        grid.insert(2, ("mxu_int8", False))
    results = []
    for backend, bind in grid:
        pol = gemm.GemmPolicy(backend=backend, k=4)
        bind_s = 0.0
        p = params
        if bind:
            t0 = time.perf_counter()
            p = model.bind_params(params, pol)
            bind_s = time.perf_counter() - t0

        def run_lockstep(per_batch: bool):
            done = 0
            for i in range(0, len(trace), slots):
                group = trace[i:i + slots]
                pl = (max(len(r.prompt) for r in group) if per_batch
                      else pl_max)
                gl = (max(r.max_new_tokens for r in group) if per_batch
                      else gl_max)
                prompts = np.stack([np.pad(r.prompt, (0, pl - len(r.prompt)))
                                    for r in group])
                lockstep_generate(cfg, model, p, jnp.asarray(prompts), gl,
                                  policy=pol)
                done += len(group) * gl
            return done

        def run_engine(paged_kernel=None):
            eng = engine_mod.ServeEngine(cfg, p, policy=pol, max_slots=slots,
                                         max_len=max_len,
                                         paged_kernel=paged_kernel)
            eng.run(list(trace))
            return eng.stats

        # warm every compile cache, then time (min over reps — the shared
        # CPU is noisy and these runs are sub-second)
        run_lockstep(False), run_lockstep(True), run_engine()
        reps = 2 if fast else 3
        lock_s = min(engine_mod.elapsed(
            lambda: run_lockstep(False))[1] for _ in range(reps))
        lock_pb_s = min(engine_mod.elapsed(
            lambda: run_lockstep(True))[1] for _ in range(reps))
        eng_s, st = np.inf, None
        for _ in range(reps):
            st_i, dt = engine_mod.elapsed(run_engine)
            if dt < eng_s:
                eng_s, st = dt, st_i
        assert st["generated_tokens"] == useful, (st, useful)
        padded = run_lockstep(False)
        row = {"cell": "engine_vs_lockstep",
               "backend": backend, "bound": bind, "bind_s": round(bind_s, 3),
               "slots": slots, "requests": n_req,
               "useful_tokens": useful, "lockstep_padded_tokens": padded,
               "lockstep_tok_per_s": round(useful / lock_s, 1),
               "lockstep_per_batch_tok_per_s": round(useful / lock_pb_s, 1),
               "engine_tok_per_s": round(useful / eng_s, 1),
               "engine_decode_steps": st["decode_steps"],
               "slot_utilization": st["slot_utilization"],
               "block_utilization": st["block_utilization"],
               "speedup": round(lock_s / eng_s, 2),
               "speedup_vs_per_batch": round(lock_pb_s / eng_s, 2)}
        results.append(row)
        print(f"serve_engine_{backend}{'_bound' if bind else ''},"
              f"{eng_s / useful * 1e6:.0f},speedup={row['speedup']}x "
              f"(vs per-batch-padded {row['speedup_vs_per_batch']}x) "
              f"engine={row['engine_tok_per_s']}tok/s "
              f"lockstep={row['lockstep_tok_per_s']}tok/s "
              f"bind={bind_s:.2f}s")

        # --- paged_kernel cell: fused in-kernel-table-walk attention --------
        # Same trace, same lockstep baseline; the engine swaps the per-layer
        # gather + wide chunked_attention for kernels.paged_attention
        # (n_splits=1, the bit-exact serving contract). `speedup` is gated by
        # benchmarks/compare.py exactly like the gather row's.
        run_engine(1)                                   # warm compile caches
        pk_s, st_pk = np.inf, None
        for _ in range(reps):
            st_i, dt = engine_mod.elapsed(lambda: run_engine(1))
            if dt < pk_s:
                pk_s, st_pk = dt, st_i
        assert st_pk["generated_tokens"] == useful, (st_pk, useful)
        row_pk = {"cell": "paged_kernel",
                  "backend": backend, "bound": bind, "slots": slots,
                  "requests": n_req, "useful_tokens": useful, "n_splits": 1,
                  "engine_tok_per_s": round(useful / pk_s, 1),
                  "gather_engine_tok_per_s": round(useful / eng_s, 1),
                  "lockstep_tok_per_s": round(useful / lock_s, 1),
                  "engine_decode_steps": st_pk["decode_steps"],
                  "speedup": round(lock_s / pk_s, 2),
                  "speedup_vs_gather": round(eng_s / pk_s, 2)}
        results.append(row_pk)
        print(f"serve_paged_kernel_{backend}{'_bound' if bind else ''},"
              f"{pk_s / useful * 1e6:.0f},speedup={row_pk['speedup']}x "
              f"(vs gather engine {row_pk['speedup_vs_gather']}x) "
              f"engine={row_pk['engine_tok_per_s']}tok/s")

    # --- capacity cell: concurrent requests at one fixed KV budget ----------
    cap_len, cap_bs, cap_slots_c = 32, 4, 4
    budget_blocks = cap_slots_c * (cap_len // cap_bs)   # contiguous budget
    n_cap = 12 if fast else 18
    cap_trace = [engine_mod.Request(
        rid=r, prompt=np.random.default_rng(r).integers(
            0, cfg.vocab_size, 6).astype(np.int32), max_new_tokens=6)
        for r in range(n_cap)]                          # 3 blocks each

    def run_cap(paged):
        if paged:
            eng = engine_mod.ServeEngine(
                cfg, params, max_slots=n_cap, max_len=cap_len,
                block_size=cap_bs, n_blocks=budget_blocks, prefill_chunk=6)
        else:
            eng = engine_mod.ServeEngine(cfg, params, max_slots=cap_slots_c,
                                         max_len=cap_len, paged=False)
        eng.run(list(cap_trace))
        return eng.stats, eng.stats["peak_active_slots"]

    run_cap(True), run_cap(False)                       # warm compile caches
    (st_p, peak_p), dt_p = engine_mod.elapsed(lambda: run_cap(True))
    (st_c, peak_c), dt_c = engine_mod.elapsed(lambda: run_cap(False))
    useful_cap = sum(r.max_new_tokens for r in cap_trace)
    row = {"cell": "capacity", "kv_budget_tokens": budget_blocks * cap_bs,
           "block_size": cap_bs, "requests": n_cap,
           "paged_peak_concurrent": int(peak_p),
           "contiguous_peak_concurrent": int(peak_c),
           "concurrency_ratio": round(peak_p / peak_c, 2),
           "paged_tok_per_s": round(useful_cap / dt_p, 1),
           "contiguous_tok_per_s": round(useful_cap / dt_c, 1),
           "paged_block_utilization": st_p["block_utilization"]}
    results.append(row)
    print(f"serve_capacity,{dt_p / useful_cap * 1e6:.0f},"
          f"paged={peak_p}req vs contiguous={peak_c}req at "
          f"{row['kv_budget_tokens']}tok budget "
          f"({row['concurrency_ratio']}x concurrency)")

    # --- chunked-prefill cell: bursty arrivals, heterogeneous prompts -------
    # Real traffic carries many distinct prompt lengths. The contiguous
    # engine's fused prefill-on-admit jit-specializes per prompt length, so a
    # bursty heterogeneous trace pays one compilation per new length *at
    # serve time*; chunked prefill feeds prompts through the shared batched
    # step and compiles at most prefill_chunk widths. Measured **cold**
    # (single shot, each path paying its own jit specializations — the
    # admission overhead the ROADMAP item targets), with steady-state warm
    # numbers recorded alongside.
    n_cp = 10 if fast else 16
    rng_cp = np.random.default_rng(7)
    t_arr = 0.0
    cp_trace = []
    for r in range(n_cp):
        t_arr += rng_cp.exponential(0.5)
        cp_trace.append(engine_mod.Request(
            rid=r,
            prompt=rng_cp.integers(0, cfg.vocab_size,
                                   8 + r).astype(np.int32),
            max_new_tokens=6, arrival=int(t_arr)))      # 16 distinct lengths

    def run_cp(paged):
        eng = engine_mod.ServeEngine(
            cfg, params, max_slots=4, max_len=8 + n_cp + 8, paged=paged,
            **({"block_size": cap_bs, "prefill_chunk": 8} if paged else {}))
        eng.run(list(cp_trace))
        return eng.stats

    useful_cp = sum(r.max_new_tokens for r in cp_trace)
    _, cold_p = engine_mod.elapsed(lambda: run_cp(True))
    _, cold_c = engine_mod.elapsed(lambda: run_cp(False))
    reps = 2 if fast else 3
    warm_p = min(engine_mod.elapsed(lambda: run_cp(True))[1]
                 for _ in range(reps))
    warm_c = min(engine_mod.elapsed(lambda: run_cp(False))[1]
                 for _ in range(reps))
    row = {"cell": "chunked_prefill", "requests": n_cp,
           "distinct_prompt_lens": n_cp, "prefill_chunk": 8,
           "chunked_tok_per_s": round(useful_cp / cold_p, 1),
           "per_request_tok_per_s": round(useful_cp / cold_c, 1),
           "speedup": round(cold_c / cold_p, 2),
           "warm_chunked_tok_per_s": round(useful_cp / warm_p, 1),
           "warm_per_request_tok_per_s": round(useful_cp / warm_c, 1)}
    results.append(row)
    print(f"serve_chunked_prefill,{cold_p / useful_cp * 1e6:.0f},"
          f"{row['speedup']}x vs per-request prefill on {n_cp} distinct "
          f"prompt lengths ({row['chunked_tok_per_s']} vs "
          f"{row['per_request_tok_per_s']} tok/s cold; warm "
          f"{row['warm_chunked_tok_per_s']} vs "
          f"{row['warm_per_request_tok_per_s']})")
    # --- multi-step cell: fused decode horizons, syncs-per-token ------------
    # Decode-heavy trace (long generations, short prompts): the regime where
    # the per-token host sync dominates the scheduler. n=1 is the per-step
    # engine; n in {4, 8} dispatch fused lax.scan horizons with on-device
    # retirement (one (n, B) token sync per horizon). `speedup` (useful
    # tok/s vs the same-run n=1 row) is gated by benchmarks/compare.py like
    # every relative metric; syncs_per_token records the 1/n sync bound.
    n_ms = 8 if fast else 12
    ms_trace = engine_mod.make_poisson_trace(
        n_ms, rate=4.0, vocab_size=cfg.vocab_size, prompt_lens=(4, 6),
        gen_lens=(48, 64, 96), seed=3)
    useful_ms = sum(r.max_new_tokens for r in ms_trace)
    ms_len = (max(len(r.prompt) for r in ms_trace)
              + max(r.max_new_tokens for r in ms_trace))
    pol_ms = gemm.GemmPolicy(backend="mxu_int8", k=4)
    p_ms = model.bind_params(params, pol_ms)

    def run_ms(n):
        eng = engine_mod.ServeEngine(cfg, p_ms, policy=pol_ms,
                                     max_slots=slots, max_len=ms_len,
                                     multi_step=n)
        fin = eng.run(list(ms_trace))
        return eng.stats, {rid: f.tokens for rid, f in fin.items()}

    base_ms, base_streams = None, None
    for n in (1, 4, 8):
        run_ms(n)                                       # warm compile caches
        ms_s, st_ms, streams = np.inf, None, None
        for _ in range(reps):
            (st_i, str_i), dt = engine_mod.elapsed(lambda: run_ms(n))
            if dt < ms_s:
                ms_s, st_ms, streams = dt, st_i, str_i
        assert st_ms["generated_tokens"] == useful_ms, (st_ms, useful_ms)
        if n == 1:
            base_ms, base_streams = ms_s, streams
        else:                                           # parity is the gate
            for rid in base_streams:
                np.testing.assert_array_equal(base_streams[rid], streams[rid])
        row = {"cell": f"multi_step_n{n}", "backend": "mxu_int8",
               "bound": True, "n": n, "slots": slots, "requests": n_ms,
               "useful_tokens": useful_ms,
               "engine_tok_per_s": round(useful_ms / ms_s, 1),
               "per_step_tok_per_s": round(useful_ms / base_ms, 1),
               "host_syncs": st_ms["host_syncs"],
               "syncs_per_token": st_ms["syncs_per_token"],
               "speedup": round(base_ms / ms_s, 2)}
        results.append(row)
        print(f"serve_multi_step_n{n},{ms_s / useful_ms * 1e6:.0f},"
              f"speedup={row['speedup']}x vs per-step "
              f"({row['engine_tok_per_s']} vs {row['per_step_tok_per_s']} "
              f"tok/s), {row['syncs_per_token']} syncs/token")

    # --- prefix-cache cells: repeated shared-prefix traffic (PR 10) ---------
    # Real serving repeats itself: one system prompt heads every request.
    # Cell 1 (prefix_cache): warm engine (block sharing on) vs cold (off) on
    # the same shared-prefix trace — `speedup` is warm-over-cold useful
    # tok/s, gated by benchmarks/compare.py. Cell 2 (prefix_capacity): at
    # one fixed small block budget, shared prefixes shrink each request's
    # fresh-block footprint, so more requests fit concurrently —
    # `concurrency_ratio` (warm peak / cold peak) is gated the same way.
    # Both cells assert stream parity in-bench: sharing must not move a bit.
    n_px = 6 if fast else 8
    rng_px = np.random.default_rng(11)
    sys_prompt = rng_px.integers(0, cfg.vocab_size, 48).astype(np.int32)
    px_trace = []
    for r in range(n_px):
        tail = rng_px.integers(0, cfg.vocab_size, 2).astype(np.int32)
        # the seeder runs alone; followers arrive once its prefill has
        # published the shared blocks (50 tokens / chunk 8 = 7 steps)
        px_trace.append(engine_mod.Request(
            rid=r, prompt=np.concatenate([sys_prompt, tail]),
            max_new_tokens=4, arrival=0 if r == 0 else 7))
    useful_px = sum(r.max_new_tokens for r in px_trace)

    def run_px(warm):
        eng = engine_mod.ServeEngine(
            cfg, params, max_slots=2, max_len=64, prefix_cache=warm)
        fin = eng.run([engine_mod.Request(
            rid=r.rid, prompt=r.prompt.copy(), max_new_tokens=r.max_new_tokens,
            arrival=r.arrival) for r in px_trace])
        return eng.stats, {rid: f.tokens for rid, f in fin.items()}

    (st_w, str_w), _ = engine_mod.elapsed(lambda: run_px(True))   # warm jit
    (st_c, str_c), _ = engine_mod.elapsed(lambda: run_px(False))
    for rid in str_c:                                   # parity is the gate
        np.testing.assert_array_equal(str_w[rid], str_c[rid])
    warm_s = min(engine_mod.elapsed(lambda: run_px(True))[1]
                 for _ in range(reps))
    cold_s = min(engine_mod.elapsed(lambda: run_px(False))[1]
                 for _ in range(reps))
    row = {"cell": "prefix_cache", "requests": n_px,
           "shared_prompt_tokens": int(len(sys_prompt)),
           "prefix_hits": st_w["prefix_hits"],
           "prefix_tokens_skipped": st_w["prefix_tokens_skipped"],
           "warm_tok_per_s": round(useful_px / warm_s, 1),
           "cold_tok_per_s": round(useful_px / cold_s, 1),
           "speedup": round(cold_s / warm_s, 2)}
    results.append(row)
    print(f"serve_prefix_cache,{warm_s / useful_px * 1e6:.0f},"
          f"speedup={row['speedup']}x warm vs cold "
          f"({row['warm_tok_per_s']} vs {row['cold_tok_per_s']} tok/s), "
          f"{row['prefix_hits']} hits / "
          f"{row['prefix_tokens_skipped']} tokens skipped")

    n_pc = 5 if fast else 7
    pc_bs, pc_blocks = 4, 12
    rng_pc = np.random.default_rng(13)
    pc_head = rng_pc.integers(0, cfg.vocab_size, 16).astype(np.int32)
    pc_trace = [engine_mod.Request(
        rid=r,
        prompt=np.concatenate(
            [pc_head, rng_pc.integers(0, cfg.vocab_size, 2).astype(np.int32)]),
        max_new_tokens=4, arrival=0 if r == 0 else 10)
        for r in range(n_pc)]                           # 6 blocks, 4 shared

    def run_pc(warm):
        eng = engine_mod.ServeEngine(
            cfg, params, max_slots=n_pc, max_len=24, block_size=pc_bs,
            n_blocks=pc_blocks, prefill_chunk=6, prefix_cache=warm)
        fin = eng.run([engine_mod.Request(
            rid=r.rid, prompt=r.prompt.copy(), max_new_tokens=r.max_new_tokens,
            arrival=r.arrival) for r in pc_trace])
        return (eng.stats["peak_active_slots"],
                {rid: f.tokens for rid, f in fin.items()})

    peak_w, pcs_w = run_pc(True)
    peak_c2, pcs_c = run_pc(False)
    for rid in pcs_c:
        np.testing.assert_array_equal(pcs_w[rid], pcs_c[rid])
    row = {"cell": "prefix_capacity", "block_budget": pc_blocks,
           "block_size": pc_bs, "requests": n_pc,
           "blocks_per_request": 6, "shared_blocks_per_request": 4,
           "warm_peak_concurrent": int(peak_w),
           "cold_peak_concurrent": int(peak_c2),
           "concurrency_ratio": round(peak_w / peak_c2, 2)}
    results.append(row)
    print(f"serve_prefix_capacity,0,"
          f"warm={peak_w}req vs cold={peak_c2}req at "
          f"{pc_blocks} blocks ({row['concurrency_ratio']}x concurrency)")

    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_serve_engine.json")
    with open(path, "w") as f:
        json.dump({"device": jax.default_backend(),
                   "mode": "interpret" if jax.default_backend() != "tpu"
                   else "mosaic",
                   "fast": fast, "arch": "smollm-360m (reduced)",
                   "note": "ragged Poisson trace; lockstep pads every batch "
                           "to its longest prompt/gen; engine = continuous "
                           "batching with per-slot ragged decode; bind_s = "
                           "vectorized gemm.bind latency",
                   "results": results}, f, indent=1)
    print(f"bench_serve_engine_json,0,{os.path.normpath(path)}")


def abft_guard_bench(fast: bool = False):
    """ABFT guard overhead: guarded vs unguarded engine decode throughput.

    Serves one fixed ragged Poisson trace through the paged engine per
    backend, with ``guard='none'`` and ``guard='detect'`` (same params, same
    compiled-step caches warmed), and records useful-tokens/s for both plus
    their same-run ratio ``guarded_frac = guarded / unguarded`` — the
    fraction of throughput that survives checksums + between-step scrubbing.
    The scheduled CI job gates on that ratio (benchmarks/compare.py,
    >20% drop fails): absolute tok/s is machine-bound, the fraction is not.
    Streams are asserted bit-identical between the two runs — the guard must
    observe, never perturb.
    """
    import json
    import os
    import jax
    from repro.configs import ARCHS, reduced
    from repro.core import gemm
    from repro.launch import engine as engine_mod
    from repro.models import get_model

    cfg = reduced(ARCHS["smollm-360m"])
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    n_req = 8 if fast else 12
    trace = engine_mod.make_poisson_trace(
        n_req, rate=3.0, vocab_size=cfg.vocab_size, prompt_lens=(4, 6),
        gen_lens=(6, 8, 12), seed=0)
    useful = sum(r.max_new_tokens for r in trace)
    backends = (("exact", False), ("approx_lut", True)) if fast else \
        (("exact", False), ("mxu_int8", True), ("approx_lut", True),
         ("approx_delta", True))
    results = []
    for backend, bind in backends:
        p = model.bind_params(params, gemm.GemmPolicy(backend=backend, k=4)) \
            if bind else params

        def run(guard):
            pol = gemm.GemmPolicy(backend=backend, k=4, guard=guard)
            eng = engine_mod.ServeEngine(cfg, p, policy=pol, max_slots=4,
                                         max_len=24)
            fin = eng.run(list(trace))
            assert eng.events["faults_detected"] == 0, eng.events
            return {rid: f.tokens for rid, f in fin.items()}

        base = run("none")
        guarded = run("detect")                 # also warms both caches
        for rid in base:
            np.testing.assert_array_equal(base[rid], guarded[rid])
        reps = 2 if fast else 3
        none_s = min(engine_mod.elapsed(lambda: run("none"))[1]
                     for _ in range(reps))
        det_s = min(engine_mod.elapsed(lambda: run("detect"))[1]
                    for _ in range(reps))
        row = {"cell": "abft_guard", "backend": backend, "bound": bind,
               "requests": n_req, "useful_tokens": useful,
               "unguarded_tok_per_s": round(useful / none_s, 1),
               "guarded_tok_per_s": round(useful / det_s, 1),
               "guarded_frac": round(none_s / det_s, 3)}
        results.append(row)
        print(f"abft_guard_{backend}{'_bound' if bind else ''},"
              f"{det_s / useful * 1e6:.0f},"
              f"guarded={row['guarded_tok_per_s']}tok/s "
              f"unguarded={row['unguarded_tok_per_s']}tok/s "
              f"({row['guarded_frac']:.0%} survives the guard)")
    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_abft.json")
    with open(path, "w") as f:
        json.dump({"device": jax.default_backend(),
                   "mode": "interpret" if jax.default_backend() != "tpu"
                   else "mosaic",
                   "fast": fast, "arch": "smollm-360m (reduced)",
                   "note": "guard='detect' vs guard='none' on one ragged "
                           "Poisson trace through the paged engine; "
                           "guarded_frac = guarded/unguarded tok/s "
                           "(same-run ratio, gated in CI)",
                   "results": results}, f, indent=1)
    print(f"bench_abft_json,0,{os.path.normpath(path)}")


def roofline_summary():
    """Dry-run roofline table (reads experiments/dryrun.jsonl if present)."""
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "experiments",
                        "dryrun.jsonl")
    if not os.path.exists(path):
        print("roofline_summary,0,skipped (run repro.launch.dryrun --all first)")
        return
    n_ok = n_skip = n_err = 0
    worst = (None, 1e9)
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            if r["status"] == "ok":
                n_ok += 1
                rf = r.get("analytic", {}).get("roofline_frac", 0)
                if r["mesh"] == "16x16" and rf < worst[1]:
                    worst = (f"{r['arch']}x{r['shape']}", rf)
            elif r["status"] == "skipped":
                n_skip += 1
            else:
                n_err += 1
    print(f"roofline_cells,0,{n_ok}ok/{n_skip}skip/{n_err}fail")
    if worst[0]:
        print(f"roofline_worst_cell,0,{worst[0]}@{worst[1]:.1%}")


def analysis_bench(fast=False):
    """Static-analysis wall time: full-repo lint + kernel audit (<10s budget).

    The CLI gate runs on every tier-1 push, so the whole pass must stay
    interactive-fast; the budget is asserted, not just reported.
    """
    import pathlib
    import time
    from repro import analysis

    root = pathlib.Path(__file__).resolve().parent.parent
    t0 = time.perf_counter()
    report = analysis.run(root=str(root))
    wall_s = time.perf_counter() - t0
    budget_s = 10.0
    assert wall_s < budget_s, (
        f"static analysis took {wall_s:.1f}s (> {budget_s:.0f}s budget) — "
        "the tier-1 CLI gate must stay interactive-fast")
    assert not report.active(), [f.format() for f in report.active()]
    meta = report.meta
    print(f"analysis_full_pass,{wall_s * 1e6:.0f},"
          f"{meta.get('lint_files', 0)}files+{meta.get('audit_cells', 0)}cells "
          f"in {wall_s:.2f}s (budget {budget_s:.0f}s, 0 findings)")


BENCHES = {
    "table1_cells": lambda fast: table1_cells(),
    "table2_cells": lambda fast: table2_cells(),
    "table3_pe": lambda fast: table3_pe(),
    "table4_sa": table4_sa,
    "table5_errors": table5_errors,
    "table6_apps": table6_apps,
    "fig9_fig10_pareto": fig9_fig10_pareto,
    "latency_wavefront": lambda fast: latency_wavefront(),
    "kernels_bench": kernels_bench,
    "gemm_backends_bench": gemm_backends_bench,
    "apps_bench": apps_bench,
    "serve_bound_bench": serve_bound_bench,
    "serve_engine_bench": serve_engine_bench,
    "abft_guard_bench": abft_guard_bench,
    "analysis_bench": analysis_bench,
    "roofline_summary": lambda fast: roofline_summary(),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("benches", nargs="*", choices=[[], *BENCHES],
                    help="benchmarks to run (default: all), e.g. "
                         "`python -m benchmarks.run serve_bound_bench`")
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    names = args.benches or list(BENCHES)
    print("name,us_per_call,derived")
    for name in names:
        BENCHES[name](args.fast)


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
