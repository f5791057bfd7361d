"""Serving driver: lockstep reference loop + continuous-batching engine CLI.

Two modes:

* **lockstep** (default) — batched prefill + greedy decode with one shared
  position: every request padded to the same prompt/gen length. This is the
  bit-parity *reference* for the engine (`lockstep_generate`) and the
  baseline the engine's throughput is measured against.
* **``--engine``** — the continuous-batching engine (`launch.engine`):
  admission queue, prefill-on-admit, per-slot ragged decode, EOS/max-len
  retirement, slot reuse, per-slot sampling. Give it a ragged workload with
  ``--requests/--poisson-rate`` (synthetic Poisson trace) or replay a
  recorded trace with ``--trace FILE`` (JSON lines:
  ``{"arrival": 3, "prompt_len": 12, "gen_len": 16, "temperature": 0.7}``;
  unknown lengths fall back to --prompt-len/--gen-len).

``--backend`` routes every model GEMM through that `GemmPolicy` backend;
``--bind`` (the default for non-exact backends) binds the parameter pytree
first (`core.gemm.bind`) so decode runs weight-stationary — weights are
quantized and backend-prepared once instead of every token.

Run:  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --debug \
          --engine --requests 8 --poisson-rate 2 --backend mxu_int8 --bind
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, reduced
from repro.core import gemm
from repro.models import get_model

from . import compile_cache
from . import engine as engine_mod
from . import sampling


def _build_lockstep_steps(cfg, policy):
    model = get_model(cfg)
    prefill = jax.jit(
        lambda p, bt, c: model.prefill(p, bt, c, policy=policy))
    decode = jax.jit(
        lambda p, t, c, pos: model.decode_step(p, t, c, pos, policy=policy))
    return prefill, decode


_cached_lockstep_steps = functools.lru_cache(maxsize=64)(_build_lockstep_steps)


def _lockstep_steps(cfg, policy):
    try:
        return _cached_lockstep_steps(cfg, policy)
    except TypeError:    # unhashable policy (dict overrides): fresh build
        return _build_lockstep_steps(cfg, policy)


def lockstep_generate(cfg, model, params, prompts, gen_len, *,
                      policy=gemm.EXACT, input_embeds=None,
                      return_logits=False):
    """The lockstep reference: batched prefill + greedy decode, one scalar
    position shared by the whole batch. Returns (B, gen_len) int32 tokens,
    and with ``return_logits`` also the (B, gen_len, V) float32 logits each
    token was picked from.

    Per-request bit-parity contract: running a request alone here (batch 1)
    produces exactly the tokens the continuous-batching engine produces for
    it under greedy sampling, whatever else shares the engine's batch.
    """
    b, pl = prompts.shape
    start = pl + (input_embeds.shape[1] if input_embeds is not None else 0)
    cache = model.init_cache(b, start + gen_len)
    batch = {"tokens": prompts}
    if input_embeds is not None:
        batch["input_embeds"] = input_embeds
    # module-level jit cache: repeated calls (bench reps, per-request parity
    # references) hit compiled executables
    prefill_j, decode_j = _lockstep_steps(cfg, policy)
    logits, cache = prefill_j(params, batch, cache)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out_tokens = [np.asarray(tok)]
    out_logits = [np.asarray(logits[:, -1:], np.float32)] if return_logits else []
    for i in range(gen_len - 1):
        logits, cache = decode_j(params, tok, cache, jnp.int32(start + i))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out_tokens.append(np.asarray(tok))
        if return_logits:
            out_logits.append(np.asarray(logits[:, -1:], np.float32))
    tokens = np.concatenate(out_tokens, axis=1)
    if return_logits:
        return tokens, np.concatenate(out_logits, axis=1)
    return tokens


def load_trace(path, vocab_size, default_prompt_len, default_gen_len, *,
               seed=0):
    """Replay a recorded request trace (JSON lines) as engine Requests."""
    rng = np.random.default_rng(seed)
    requests = []
    with open(path) as f:
        for rid, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            plen = int(rec.get("prompt_len", default_prompt_len))
            requests.append(engine_mod.Request(
                rid=rid,
                prompt=rng.integers(0, vocab_size, plen).astype(np.int32),
                max_new_tokens=int(rec.get("gen_len", default_gen_len)),
                params=sampling.SamplingParams(
                    temperature=float(rec.get("temperature", 0.0)),
                    top_k=int(rec.get("top_k", 0)),
                    top_p=float(rec.get("top_p", 1.0)),
                    seed=int(rec.get("seed", 0))),
                arrival=int(rec.get("arrival", 0))))
    return requests


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--debug", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="lockstep batch size / engine slot count")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--backend", default="exact", choices=gemm.BACKENDS,
                    help="GemmPolicy backend for every model GEMM")
    ap.add_argument("--k", type=int, default=4, help="approximation factor")
    ap.add_argument("--guard", default="none", choices=gemm.GUARDS,
                    help="ABFT integrity checking on every GEMM: 'detect' "
                         "flags faults (the engine restores/quarantines), "
                         "'recompute' additionally re-executes flagged tiles")
    ap.add_argument("--bind", action="store_true",
                    help="bind params to the policy (weight-stationary decode)")
    ap.add_argument("--no-bind", dest="bind", action="store_false")
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine instead of lockstep")
    ap.add_argument("--requests", type=int, default=0,
                    help="engine: synthetic Poisson trace of N requests")
    ap.add_argument("--poisson-rate", type=float, default=2.0,
                    help="engine: mean arrivals per decode step")
    ap.add_argument("--trace", default=None,
                    help="engine: replay a JSONL request trace")
    ap.add_argument("--max-len", type=int, default=0,
                    help="engine: per-slot cache length (default: "
                         "prompt-len + gen-len)")
    ap.add_argument("--paged", dest="paged", action="store_true", default=True,
                    help="engine: paged KV cache + chunked prefill (default)")
    ap.add_argument("--contiguous", dest="paged", action="store_false",
                    help="engine: PR-4 contiguous per-slot caches with "
                         "whole-prompt prefill-on-admit")
    ap.add_argument("--block-size", type=int, default=8,
                    help="engine: paged KV block size in tokens")
    ap.add_argument("--n-blocks", type=int, default=0,
                    help="engine: KV pool size in blocks (default: the "
                         "contiguous budget, slots * ceil(max_len / block))")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="engine: prompt tokens admitted per chunked-prefill "
                         "step")
    ap.add_argument("--paged-kernel", type=int, default=0, metavar="N",
                    help="engine: serve attention through the fused Pallas "
                         "paged-attention kernel; N=1 keeps the bit-exact "
                         "sequential KV scan, N>1 enables split-KV flash "
                         "decoding with N splits (0 = gather path)")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=True,
                    help="engine: share KV blocks across requests with equal "
                         "prompt prefixes — hash-keyed block index, "
                         "copy-on-write, LRU eviction (default; paged only)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false",
                    help="engine: disable prefix caching (every admission "
                         "re-prefills from token zero)")
    ap.add_argument("--multi-step", type=int, default=1, metavar="N",
                    help="engine: fuse N decode sub-steps into one "
                         "device-resident lax.scan horizon (on-device "
                         "EOS/budget retirement, one host sync per horizon; "
                         "1 = per-step dispatch)")
    ap.add_argument("--queue-limit", type=int, default=0,
                    help="engine: bound the admission queue — overflow is "
                         "rejected with status 'rejected_queue_full' "
                         "(0 = unbounded)")
    ap.add_argument("--ttft-deadline", type=int, default=0,
                    help="engine: retire requests that have not emitted a "
                         "first token within N steps of arrival (0 = off)")
    ap.add_argument("--total-deadline", type=int, default=0,
                    help="engine: retire requests not finished within N "
                         "steps of arrival (0 = off)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.set_defaults(bind=None)
    args = ap.parse_args(argv)

    cfg = ARCHS[args.arch]
    if args.debug:
        cfg = reduced(cfg)
    if cfg.family == "audio":
        raise SystemExit("encoder-only arch has no decode step")
    policy = gemm.GemmPolicy(backend=args.backend, k=args.k, guard=args.guard)
    do_bind = (args.backend != "exact") if args.bind is None else args.bind
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    if do_bind:
        t0 = time.time()
        params = model.bind_params(params, policy)
        print(f"bound params to backend={args.backend} in "
              f"{time.time() - t0:.2f}s (weight-stationary decode)")
    rng = np.random.default_rng(args.seed)

    if args.engine:
        sp = sampling.SamplingParams(temperature=args.temperature,
                                     top_k=args.top_k, top_p=args.top_p,
                                     seed=args.seed)
        if args.trace:
            requests = load_trace(args.trace, cfg.vocab_size, args.prompt_len,
                                  args.gen_len, seed=args.seed)
        else:
            n = args.requests or 2 * args.batch
            requests = engine_mod.make_poisson_trace(
                n, rate=args.poisson_rate, vocab_size=cfg.vocab_size,
                prompt_lens=(args.prompt_len,), gen_lens=(args.gen_len,),
                seed=args.seed, params=sp)
        max_len = args.max_len or (args.prompt_len + args.gen_len)
        kw = {}
        if args.paged:
            kw = {"block_size": args.block_size,
                  "n_blocks": args.n_blocks or None,
                  "prefill_chunk": args.prefill_chunk,
                  "paged_kernel": args.paged_kernel or None,
                  "prefix_cache": args.prefix_cache}
        if args.ttft_deadline or args.total_deadline:
            for r in requests:
                r.ttft_deadline = args.ttft_deadline or None
                r.total_deadline = args.total_deadline or None
        eng = engine_mod.ServeEngine(cfg, params, policy=policy,
                                     max_slots=args.batch, max_len=max_len,
                                     eos_id=args.eos_id, paged=args.paged,
                                     queue_limit=args.queue_limit or None,
                                     multi_step=args.multi_step,
                                     **kw)
        t0 = time.time()
        finished = eng.run(requests)
        dt = time.time() - t0
        st = eng.stats
        print(f"engine: {st['finished']} requests, "
              f"{st['generated_tokens']} tokens in {dt:.2f}s "
              f"({st['generated_tokens'] / dt:.1f} tok/s) over "
              f"{st['decode_steps']} decode steps")
        print(f"host syncs: {st['host_syncs']} "
              f"({st['syncs_per_token']:.3f}/token, "
              f"multi_step={st['multi_step']})")
        per_step = 1.0 / max(1, st["steps"])
        print("step phases: " + " ".join(
            f"{p} {st[p + '_ms'] * per_step:.3f}"
            for p in ("admit", "prepare", "dispatch", "sync", "commit"))
            + f" ms/step; queue wait {st['queue_wait_mean_ms']:.3f} ms mean")
        if st["delta_forms"]:
            print("approx_delta lookup sites: " + ", ".join(
                f"{form} {n}" for form, n in sorted(st["delta_forms"].items())))
        if args.paged:
            tok_total = max(1, st["prefill_tokens"] + st["decode_tokens"])
            print(f"occupancy: slots {st['slot_utilization']:.1%} "
                  f"(peak {st['peak_active_slots']}/{args.batch}), "
                  f"cache blocks {st['block_utilization']:.1%} "
                  f"(peak {st['peak_allocated_blocks']}/"
                  f"{eng.pool.spec.n_blocks}), "
                  f"token split {st['prefill_tokens']}/{st['decode_tokens']} "
                  f"prefill/decode "
                  f"({st['prefill_tokens'] / tok_total:.0%} prefill)")
            if eng.prefix_cache:
                print(f"prefix cache: {st['prefix_hits']} hits, "
                      f"{st['prefix_tokens_skipped']} prompt tokens skipped, "
                      f"{st['prefix_shared_blocks']} blocks shared, "
                      f"{st['prefix_cow_copies']} COW copies, "
                      f"{st['prefix_evicted_blocks']} evicted, "
                      f"{st['prefix_cached_blocks']} cached now")
        rel = {k: st[k] for k in (engine_mod.REJECTED_QUEUE_FULL, "cancelled",
                                  "deadline_ttft", "deadline_total",
                                  "preemptions", "faults_detected",
                                  "step_retries", "quarantines")}
        if args.guard != "none" or any(rel.values()):
            print("reliability: " + ", ".join(f"{k}={v}"
                                              for k, v in rel.items()))
        for rid in sorted(finished)[:4]:
            f = finished[rid]
            print(f"  rid={rid} [{f.finish_reason}] "
                  f"tokens={f.tokens[:8].tolist()}...")
        return finished

    b, pl, gl = args.batch, args.prompt_len, args.gen_len
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, pl)), jnp.int32)
    input_embeds = None
    if cfg.family == "vlm":
        input_embeds = jnp.asarray(
            rng.normal(size=(b, max(2, pl // 4), cfg.d_model)), jnp.float32)
    t0 = time.time()
    gen = lockstep_generate(cfg, model, params, prompts, gl, policy=policy,
                            input_embeds=input_embeds)
    dt = time.time() - t0
    print(f"generated {gen.shape} tokens in {dt:.2f}s "
          f"({b * gl / dt:.1f} tok/s); first row: {gen[0][:12]}")
    return gen


if __name__ == "__main__":
    compile_cache.enable()
    main()
