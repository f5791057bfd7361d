"""Continuous-batching serve engine: slot scheduler over one batched decode.

The lockstep serving loop pads every request to the batch's slowest one — a
whole batch stalls on its longest generation and re-fills only between
batches. This engine instead keeps a fixed pool of **slots** (the batch rows
of one jit'd decode step) continuously busy under ragged real-world traffic:

* **admission queue** — submitted requests wait FIFO; a request is admitted
  as soon as a slot is free (and, in trace replay, its arrival step has
  passed — full-queue backpressure is just the queue outlasting the pool).
* **paged KV cache** (default) — full-attention KV lives in a shared block
  pool (`launch.paged.BlockPool`): admission reserves a request's worst-case
  block footprint (backpressuring on the *pool*, not on `slots x max_len`
  contiguous regions), blocks are allocated on first write and freed at
  retirement. At a fixed HBM budget concurrency is bounded by the tokens
  requests actually hold, not by the per-slot maximum.
* **chunked prefill** — prompts stream through the *same* jit'd batched step
  as decode, in chunks of `prefill_chunk` tokens: a step's batch mixes
  prompt chunks and single decode tokens (per-slot `q_len`), so admission
  never dispatches a one-request prefill and bursty arrivals batch their
  prompt work. The final chunk samples the request's first token with the
  same RNG stream the fused admit used to.
* **per-slot ragged decode** — one jit'd step decodes all slots at their own
  `positions: (B,)`, writes each slot's KV/SSM state at its own offset, and
  samples each slot under its own parameters and RNG stream
  (`launch.sampling`). Inactive slots ride along as masked garbage: their
  writes are redirected to the pool's dump block and their state is wiped at
  the next admit (`models.api.reset_slot`).
* **retirement & slot reuse** — a slot retires on EOS or on its request's
  token budget, returns its blocks to the pool, and is immediately
  available to the admission loop.

``paged=False`` keeps the PR-4 contiguous engine: per-slot `max_len` cache
regions, fused whole-prompt prefill-on-admit — the baseline the capacity
benchmark compares against, bit-identical streams to the paged engine.

**Request lifecycle hardening** (see docs/serving.md "Reliability"):
bounded admission queue with an explicit ``rejected_queue_full`` status,
per-request TTFT / total-latency deadlines in engine steps (deterministic —
no wall clocks in scheduling decisions), client cancellation that frees the
slot and its blocks immediately, and priority admission with
preempt-and-requeue under block-pool exhaustion: a higher-priority arrival
may evict the most-recently-admitted lower-priority slot, whose request is
requeued and later **replayed from its prompt bit-identically** (the
determinism contract above makes preemption invisible in the stream). A
preempted request's effective priority is aged up by one per preemption, so
sustained high-priority pressure cannot starve it forever. With
``policy.guard != 'none'`` the paged engine additionally scrubs its bound
params and KV pool between steps (bit-level fingerprints, core/abft.py),
drains the ABFT fault ledger after every step, and recovers: params faults
restore from the init-time pristine snapshot and re-dispatch (bounded
retries), cache faults quarantine the pool — every active request is
requeued and the pool reinitialized, streams again bit-identical on replay.

Per-request determinism: activations are quantized per-row (`core.gemm.dot`),
attention/caches are per-slot, MoE serving dispatch runs at full capacity,
recurrent and ring state advances per token under a validity mask (so prompt
chunking cannot move a bit), and sampling keys are per-request — so each
request's token stream is bit-identical to running it alone through the
lockstep loop (`launch.serve.lockstep_generate`), for every GEMM backend,
with raw or `gemm.bind`-bound params. See docs/serving.md.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import abft, error_delta
from repro.core.gemm import EXACT, GemmPolicy
from repro.models import api as model_api
from repro.train.fault import TransientError
from . import paged as paged_mod
from . import sampling
from . import spans
from . import steps as steps_mod

PyTree = Any

# Retirement status code for requests bounced by a full admission queue.
REJECTED_QUEUE_FULL = "rejected_queue_full"

# Engine default for the retirement-time BlockPool.check() invariant sweep.
# Off in production (O(pool) asserts per retirement); the test suite turns it
# on globally via conftest so every engine test doubles as a leak detector.
VALIDATE_POOL_DEFAULT = False


def _build_steps(cfg: ModelConfig, policy: GemmPolicy):
    """Jitted engine steps: fused admit (prefill + slot scatter + first-token
    sample + slot-state writes — one dispatch per admission) and fused decode
    (batched ragged decode + per-slot sample + device-side position/counter
    advance — one dispatch per token). All slot state stays device-resident;
    the scheduler only syncs the sampled tokens back each step."""
    model = model_api.get_model(cfg)

    def admit(params, batch, big_cache, zero_cache1, slot, start_pos, state,
              new_temp, new_topk, new_topp, new_key, new_eos, new_budget):
        logits, cache1 = model.prefill(params, batch, zero_cache1,
                                       policy=policy)
        axes = model_api.cache_batch_axes(big_cache)
        big_cache = {
            key: jax.lax.dynamic_update_slice_in_dim(
                big_cache[key], cache1[key].astype(big_cache[key].dtype),
                slot, axis=axes[key])
            for key in big_cache
        }
        # token i of a request always samples with fold_in(base_key, i):
        # the first (prefill) token is i=0, decode tokens fold the counter
        first = sampling.sample_tokens(
            logits[:, -1].astype(jnp.float32), new_temp[None],
            new_topk[None], new_topp[None],
            jax.random.fold_in(new_key, 0)[None])[0]
        state = dict(
            state,
            positions=state["positions"].at[slot].set(start_pos),
            counters=state["counters"].at[slot].set(1),
            last_tok=state["last_tok"].at[slot, 0].set(first),
            active=state["active"].at[slot].set(True),
            temperature=state["temperature"].at[slot].set(new_temp),
            top_k=state["top_k"].at[slot].set(new_topk),
            top_p=state["top_p"].at[slot].set(new_topp),
            keys=state["keys"].at[slot].set(new_key),
            eos=state["eos"].at[slot].set(new_eos),
            budget=state["budget"].at[slot].set(new_budget))
        return first, big_cache, state

    def decode(params, cache, state):
        logits, cache = model.decode_step(params, state["last_tok"], cache,
                                          state["positions"], policy=policy)
        keys = jax.vmap(jax.random.fold_in)(state["keys"], state["counters"])
        next_tok = sampling.sample_tokens(logits[:, 0].astype(jnp.float32),
                                          state["temperature"],
                                          state["top_k"], state["top_p"],
                                          keys)
        inc = state["active"].astype(jnp.int32)
        state = dict(state,
                     positions=state["positions"] + inc,
                     counters=state["counters"] + inc,
                     last_tok=next_tok[:, None])
        return next_tok, cache, state

    def retire(state, slot):
        return dict(state, active=state["active"].at[slot].set(False))

    return jax.jit(admit), jax.jit(decode), jax.jit(retire)


def _build_paged_steps(cfg: ModelConfig, policy: GemmPolicy,
                       paged_kernel=None):
    """Jitted paged-engine steps: one fused **chunk step** (mixed
    prefill+decode batch -> per-slot sample + device-side state advance; jit
    specializes per chunk width T, bounded by `prefill_chunk` distinct
    widths — the step narrows to the widest live chunk), a fused **admit** (slot state + per-slot cache
    wipe), and the retire flag-flip. The scheduler syncs one sampled-token
    vector per step, exactly like the contiguous engine.

    ``paged_kernel`` routes the step's paged-attention reads through the
    fused Pallas kernel (`kernels.paged_attention`) instead of the
    block-table gather path — bit-identical streams at n_splits == 1."""
    step_fn = steps_mod.make_chunk_step(cfg, policy,
                                        paged_kernel=paged_kernel)

    def chunk(params, tokens, cache, state, q_len, emit, input_embeds=None,
              embed_mask=None):
        logits, cache = step_fn(params, tokens, cache, state["positions"],
                                q_len, input_embeds, embed_mask)
        # token i of a request samples with fold_in(base_key, i): the final
        # prefill chunk emits token 0, decode steps fold the counter
        keys = jax.vmap(jax.random.fold_in)(state["keys"], state["counters"])
        tok = sampling.sample_tokens(logits[:, 0].astype(jnp.float32),
                                     state["temperature"], state["top_k"],
                                     state["top_p"], keys)
        state = dict(
            state,
            positions=state["positions"] + q_len,
            counters=state["counters"] + emit.astype(jnp.int32),
            last_tok=jnp.where(emit, tok, state["last_tok"][:, 0])[:, None])
        return tok, cache, state

    def admit(cache, state, slot, start_pos, new_temp, new_topk, new_topp,
              new_key, new_eos, new_budget):
        # start_pos > 0 resumes a cached prefix: the slot's table already
        # maps the shared blocks, so prefill picks up at the boundary
        cache = model_api.reset_slot(cache, slot)
        state = dict(
            state,
            positions=state["positions"].at[slot].set(start_pos),
            counters=state["counters"].at[slot].set(0),
            active=state["active"].at[slot].set(True),
            temperature=state["temperature"].at[slot].set(new_temp),
            top_k=state["top_k"].at[slot].set(new_topk),
            top_p=state["top_p"].at[slot].set(new_topp),
            keys=state["keys"].at[slot].set(new_key),
            eos=state["eos"].at[slot].set(new_eos),
            budget=state["budget"].at[slot].set(new_budget))
        return cache, state

    def retire(state, slot):
        return dict(state, active=state["active"].at[slot].set(False))

    return jax.jit(chunk), jax.jit(admit), jax.jit(retire)


def _build_multi_step(cfg: ModelConfig, policy: GemmPolicy, n: int,
                      paged_kernel=None):
    """Jitted fixed-horizon dispatcher (`steps.make_multi_step`): one scan
    covers ``n`` decode sub-steps with device-resident EOS/budget
    retirement; the scheduler syncs one ``(n, B)`` token block per horizon
    instead of one token vector per step."""
    return jax.jit(steps_mod.make_multi_step(cfg, policy, n,
                                             paged_kernel=paged_kernel))


_cached_build_steps = functools.lru_cache(maxsize=64)(_build_steps)
_cached_build_paged = functools.lru_cache(maxsize=64)(_build_paged_steps)
_cached_build_multi = functools.lru_cache(maxsize=64)(_build_multi_step)


def cached_multi_step(cfg: ModelConfig, policy: GemmPolicy, n: int,
                      paged_kernel=None):
    """`_build_multi_step` memoized by (cfg, policy, n, paged_kernel) — same
    executable-sharing contract as `cached_steps`."""
    try:
        return _cached_build_multi(cfg, policy, n, paged_kernel=paged_kernel)
    except TypeError:
        return _build_multi_step(cfg, policy, n, paged_kernel=paged_kernel)


def cached_steps(cfg: ModelConfig, policy: GemmPolicy, paged: bool = False,
                 paged_kernel=None):
    """`_build_steps` memoized by (cfg, policy[, paged_kernel]) so every
    engine instance (and benchmark rep) reuses the compiled executables.
    Policies with dict overrides are unhashable and fall back to a fresh
    build."""
    kw = {"paged_kernel": paged_kernel} if paged else {}
    build = _cached_build_paged if paged else _cached_build_steps
    try:
        return build(cfg, policy, **kw)
    except TypeError:
        return (_build_paged_steps if paged else _build_steps)(cfg, policy,
                                                               **kw)


@dataclasses.dataclass
class Request:
    """One generation request.

    `arrival` is in engine *steps* (trace replay): the request becomes
    admissible once the engine has taken that many steps. `eos_id` overrides
    the engine-level EOS token for this request (None = engine default).

    `priority` orders admission (higher wins; equal priorities keep exact
    FIFO order) and qualifies the request to preempt strictly-lower-priority
    slots when the block pool is exhausted. `ttft_deadline` /
    `total_deadline` are budgets in engine *steps from arrival*: a request
    that has not emitted its first token (resp. retired) within the budget
    is retired with status ``deadline_ttft`` / ``deadline_total``.
    `preempt_count` is engine-maintained aging state: each preemption raises
    the request's effective priority by one, so it cannot starve.
    """
    rid: int
    prompt: np.ndarray                      # (P,) int32 prompt tokens
    max_new_tokens: int
    params: sampling.SamplingParams = sampling.GREEDY
    arrival: int = 0
    eos_id: Optional[int] = None
    input_embeds: Optional[np.ndarray] = None   # vlm: (S_img, d) patch embeds
    priority: int = 0
    ttft_deadline: Optional[int] = None
    total_deadline: Optional[int] = None
    preempt_count: int = 0                  # engine-maintained (aging)
    queued_ns: int = 0                      # engine-maintained: queued (ns)


@dataclasses.dataclass
class FinishedRequest:
    rid: int
    tokens: np.ndarray                      # (n,) int32 generated tokens
    prompt_len: int                         # incl. vlm patch positions
    admitted_step: int                      # -1 if never admitted
    finished_step: int
    finish_reason: str    # "eos" | "length" | "deadline_ttft" |
    #                       "deadline_total" | "cancelled" |
    #                       "rejected_queue_full"
    preemptions: int = 0                    # times preempted before finishing


class ServeEngine:
    """Slot-based continuous batching for any decode-capable model family.

    ``paged=True`` (default) serves from a paged KV cache with chunked
    prefill: ``block_size`` tokens per block, ``n_blocks`` pool blocks
    (default: the contiguous budget, ``max_slots * ceil(max_len /
    block_size)`` — shrink it, or raise ``max_slots`` at the same pool, to
    trade per-slot headroom for concurrency), ``prefill_chunk`` prompt
    tokens admitted per step. ``paged=False`` is the PR-4 contiguous
    engine; both produce bit-identical per-request streams.

    ``paged_kernel`` (paged mode only) serves attention reads through the
    fused Pallas paged-attention kernel — the block table is walked *inside*
    the kernel, so no gather materializes KV in HBM and each slot's scan
    stops at its live length. ``True``/``1`` keeps the sequential KV scan
    (streams stay bit-identical to the gather path and to solo lockstep);
    an int > 1 enables split-KV flash decoding with that many splits
    (log-sum-exp combine — tolerance-level parity, long contexts only).
    See `launch.autotune.paged_kernel_plan` for picking the split count.

    ``multi_step=n`` (n > 1) fuses ``n`` decode sub-steps into one
    device-resident ``lax.scan`` horizon (`steps.make_multi_step`): EOS and
    budget retirement run on device, the host syncs one ``(n, B)`` token
    block per horizon instead of one vector per token, and scheduler
    bookkeeping (admission, deadlines, retirement) runs at horizon
    boundaries only. Streams stay bit-identical to ``multi_step=1`` and to
    solo lockstep; mixed prefill/decode steps fall back to the per-step
    path automatically. See docs/serving.md "Multi-step dispatch".

    ``prefix_cache`` (paged mode, default on) shares KV blocks across
    requests with equal prompt prefixes: admission matches a rolling-hash
    key chain against resident blocks, attaches every leading hit to the
    new slot's table, and prefills only the uncached tail; retirement
    parks unreferenced cached blocks in an LRU evicted only under pool
    pressure, and writes into shared blocks copy-on-write. Streams stay
    bit-identical to an uncached run (the resumed prefill recomputes the
    last prompt position, and block contents are a pure function of the
    chain key). Automatically disabled for families with per-slot cache
    state outside the pool (gemma3 ring buffers, hybrid SSM, xLSTM); VLM
    requests carrying ``input_embeds`` are skipped per-request. See
    docs/serving.md "Prefix caching".
    """

    def __init__(self, cfg: ModelConfig, params: PyTree, *,
                 policy: GemmPolicy = EXACT, max_slots: int = 4,
                 max_len: int = 64, eos_id: Optional[int] = None,
                 paged: bool = True, block_size: int = 8,
                 n_blocks: Optional[int] = None, prefill_chunk: int = 8,
                 paged_kernel=None, queue_limit: Optional[int] = None,
                 validate_pool: Optional[bool] = None,
                 max_step_retries: int = 2, retry_backoff_s: float = 0.0,
                 retry_backoff_cap_s: float = 1.0, multi_step: int = 1,
                 prefix_cache: bool = True):
        if cfg.family == "audio":
            raise ValueError("encoder-only arch has no decode step")
        if paged_kernel and not paged:
            raise ValueError("paged_kernel requires paged=True (the fused "
                             "kernel reads through block tables)")
        if multi_step < 1:
            raise ValueError(f"multi_step must be >= 1, got {multi_step}")
        self.cfg = cfg
        self.params = params
        self.policy = policy
        self.queue_limit = queue_limit
        self.validate_pool = (VALIDATE_POOL_DEFAULT if validate_pool is None
                              else validate_pool)
        self.max_step_retries = max_step_retries
        self.retry_backoff_s = retry_backoff_s
        self.retry_backoff_cap_s = retry_backoff_cap_s
        self.multi_step = multi_step
        self.model = model_api.get_model(cfg)
        self.n_slots = max_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.paged = paged
        self.paged_kernel = paged_kernel
        # cumulative counters: each step phase's self time (``<phase>_ns``,
        # `spans.Phases`) and the queue's admissions with their summed wait
        # from submit; the paged engine adds its occupancy counters
        self.occ: Dict[str, int] = {"queue_wait_ns": 0, "admitted": 0}
        self._phase = spans.Phases(self.occ)

        if paged:
            spec = (paged_mod.PagedSpec(n_blocks, block_size)
                    if n_blocks is not None
                    else paged_mod.default_spec(max_slots, max_len, block_size))
            self.pool = paged_mod.BlockPool(spec, max_slots, max_len)
            self.cache = self.model.init_paged_cache(
                max_slots, max_len, spec.n_blocks, spec.block_size)
            self.prefill_chunk = max(1, prefill_chunk)
            # per-slot prefill cursor (None once the slot is decoding) and
            # host mirror of the device-side write position
            self.slot_prefill_off: List[Optional[int]] = [None] * max_slots
            self.slot_pos = np.zeros(max_slots, np.int64)
            self._tables_dev = None          # device mirror, rebuilt on change
            self._dev_cache = {}             # step-input mirrors, see _dev_cached
            self.occ.update({"slot_steps": 0, "slot_active_steps": 0,
                             "block_steps": 0, "block_alloc_steps": 0,
                             "prefill_tokens": 0, "decode_tokens": 0})
            # prefix caching is sound only when every cache leaf lives in
            # the shared pool: families with per-slot state outside it (ring
            # buffers, SSM/xLSTM recurrent state) can't resume mid-prompt
            # from shared blocks alone, so the cache degrades to off
            pool_pure = isinstance(self.cache, dict) and all(
                key == "block_tables" or key in model_api.PAGED_POOL_LEAVES
                for key in self.cache)
            self.prefix_cache = bool(prefix_cache and pool_pure)
            self._prefix_seed = paged_mod.cache_seed(cfg, policy)
            self._copy_blocks = steps_mod.make_copy_blocks_step()
            self.slot_chain: List[Sequence[bytes]] = [()] * max_slots
            self.slot_cacheable = [False] * max_slots
            self.prefix_events = {"prefix_hits": 0,
                                  "prefix_tokens_skipped": 0,
                                  "prefix_invalidations": 0}
        else:
            self.prefix_cache = False
            self.cache = self.model.init_cache(max_slots, max_len)
            # a pristine single-slot cache reused (never mutated) by every admit
            self._zero_cache1 = self.model.init_cache(1, max_len)

        b = max_slots
        # device-resident per-slot state, touched only inside the jitted
        # admit/decode/retire steps — the scheduler syncs one token vector
        # per step and keeps small host mirrors for its own bookkeeping
        self.state = {
            "positions": jnp.zeros(b, jnp.int32),  # next cache write offset
            "counters": jnp.zeros(b, jnp.int32),   # sampled tokens per slot
            "last_tok": jnp.zeros((b, 1), jnp.int32),
            "active": jnp.zeros(b, bool),
            "temperature": jnp.zeros(b, jnp.float32),
            "top_k": jnp.zeros(b, jnp.int32),
            "top_p": jnp.ones(b, jnp.float32),
            "keys": jnp.zeros((b, 2), jnp.uint32),
            # device-resident retirement (multi-step horizons): per-slot EOS
            # id (-1 = none) and clamped token budget — the scan flips
            # `active` itself when a slot finishes mid-horizon
            "eos": jnp.full(b, -1, jnp.int32),
            "budget": jnp.zeros(b, jnp.int32),
        }
        self.active = np.zeros(b, bool)            # host mirror
        self.slot_req: List[Optional[Request]] = [None] * b
        self.slot_out: List[List[int]] = [[] for _ in range(b)]
        self.slot_admitted = np.zeros(b, np.int32)

        self.queue: "collections.deque[Request]" = collections.deque()
        self.finished: Dict[int, FinishedRequest] = {}
        self.step_count = 0
        self.decode_steps = 0
        self.peak_active = 0                 # measured, both engine modes
        self.host_syncs = 0                  # token-block device->host syncs
        # reliability counters, surfaced through `stats` and serve.py
        self.events = {REJECTED_QUEUE_FULL: 0, "cancelled": 0,
                       "deadline_ttft": 0, "deadline_total": 0,
                       "preemptions": 0, "faults_detected": 0,
                       "step_retries": 0, "quarantines": 0}

        if paged:
            self._chunk, self._admit_paged_step, self._retire = cached_steps(
                cfg, policy, paged=True, paged_kernel=paged_kernel)
        else:
            self._admit_step, self._decode, self._retire = cached_steps(cfg,
                                                                        policy)
        if multi_step > 1:
            self._multi = cached_multi_step(
                cfg, policy, multi_step,
                paged_kernel=paged_kernel if paged else None)

        # ABFT scrub state: pristine params reference (JAX arrays are
        # immutable, so an injected flip *replaces* leaves on self.params and
        # this snapshot stays clean — restore is a reference swap) plus
        # bit-level fingerprints of params and the KV cache, re-verified
        # before every step
        self._guard = policy.guard != "none"
        if self._guard:
            self._pristine_params = params
            self._params_fp = abft.tree_fingerprint(params)
            self._cache_fp = abft.tree_fingerprint(self._scrub_view())

    # --- scheduler ----------------------------------------------------------

    def submit(self, request: Request) -> bool:
        """Queue a request. With `queue_limit` set, a full queue rejects it
        immediately with status ``rejected_queue_full`` (visible in
        `finished` and the `events` counters) instead of blocking silently.
        Returns False iff rejected."""
        if (self.queue_limit is not None
                and len(self.queue) >= self.queue_limit):
            self._finish_unstarted(request, REJECTED_QUEUE_FULL)
            return False
        request.queued_ns = time.perf_counter_ns()
        self.queue.append(request)
        return True

    def cancel(self, rid: int) -> bool:
        """Client cancellation: retire the request now with status
        ``cancelled``, freeing its slot and blocks immediately (queued
        requests are simply removed). Tokens generated so far are kept in
        the `FinishedRequest`. Returns False if `rid` is not live."""
        for slot in np.flatnonzero(self.active):
            req = self.slot_req[slot]
            if req is not None and req.rid == rid:
                self._retire_slot(slot, "cancelled")
                return True
        for req in self.queue:
            if req.rid == rid:
                self.queue.remove(req)
                self._finish_unstarted(req, "cancelled")
                return True
        return False

    def _finish_unstarted(self, req: Request, reason: str) -> None:
        self.finished[req.rid] = FinishedRequest(
            req.rid, np.zeros(0, np.int32), self._start_len(req), -1,
            self.step_count, reason, preemptions=req.preempt_count)
        if reason in self.events:
            self.events[reason] += 1

    def _start_len(self, req: Request) -> int:
        n = len(req.prompt)
        if req.input_embeds is not None:
            n += req.input_embeds.shape[0]
        return n

    def _eos_of(self, req: Request) -> int:
        """Effective EOS token id, ``-1`` = none. The host-side retirement
        check and the device-resident retirement mask (multi-step horizons)
        are both driven by this value, so their decisions provably agree."""
        eos = req.eos_id if req.eos_id is not None else self.eos_id
        return -1 if eos is None else int(eos)

    def _reserved_blocks(self, req: Request) -> int:
        """Worst-case block footprint: prompt + clamped budget, minus the
        final token whose KV is never written."""
        return self.pool.spec.blocks_for(self._start_len(req)
                                         + self._budget(req) - 1)

    def _cacheable(self, req: Request) -> bool:
        """Per-request prefix-cache eligibility: VLM requests with patch
        embeds have non-token prompt content the key chain can't identify."""
        return self.prefix_cache and req.input_embeds is None

    def _prefix_plan(self, req: Request):
        """(chain keys, hit blocks, extra COW budget, resume offset).

        Resuming at ``min(cached, start - 1)`` — never ``start`` — keeps the
        first sampled token bit-identical to a cold prefill: the final chunk
        recomputes at least the last prompt position's logits under the
        exact per-request stream. When the whole prompt is cached that one
        recomputed position rewrites the final attached block, the one
        deterministic COW site admission budgets an extra fresh block for.
        """
        if not self._cacheable(req):
            return (), [], 0, 0
        start = self._start_len(req)
        bs = self.pool.spec.block_size
        keys = paged_mod.chain_keys(self._prefix_seed, req.prompt, bs,
                                    start // bs)
        hits = self.pool.match_prefix(keys)
        cached = len(hits) * bs
        resume = min(cached, start - 1)
        extra_cow = 1 if cached >= start else 0
        return keys, hits, extra_cow, resume

    def _admit_paged(self, slot: int, req: Request, plan=None) -> None:
        start = self._start_len(req)
        if start > self.max_len:
            raise ValueError(f"request {req.rid}: prompt length {start} "
                             f"exceeds max_len {self.max_len}")
        keys, hits, extra_cow, resume = (self._prefix_plan(req)
                                         if plan is None else plan)
        self.pool.reserve(slot, self._reserved_blocks(req), hits=hits,
                          extra_cow=extra_cow, written=resume)
        if hits:
            self._tables_dev = None          # attach rewrote the table row
            self.prefix_events["prefix_hits"] += 1
            self.prefix_events["prefix_tokens_skipped"] += resume
        sp = req.params
        self.cache, self.state = self._admit_paged_step(
            self.cache, self.state, slot, jnp.int32(resume),
            jnp.float32(sp.temperature),
            jnp.int32(sp.top_k), jnp.float32(sp.top_p),
            sampling.request_key(sp.seed, req.rid),
            jnp.int32(self._eos_of(req)), jnp.int32(self._budget(req)))
        self.active[slot] = True
        self.slot_req[slot] = req
        self.slot_out[slot] = []
        self.slot_admitted[slot] = self.step_count
        self.slot_prefill_off[slot] = resume
        self.slot_pos[slot] = resume
        self.slot_chain[slot] = keys
        self.slot_cacheable[slot] = self._cacheable(req)
        self._count_admission(req)
        if self._guard:                      # admit wiped the slot's cache
            self._refresh_cache_fp()

    def _admit(self, slot: int, req: Request) -> None:
        start = self._start_len(req)
        if start > self.max_len:
            raise ValueError(f"request {req.rid}: prompt length {start} "
                             f"exceeds max_len {self.max_len}")
        batch = {"tokens": jnp.asarray(req.prompt[None], jnp.int32)}
        if req.input_embeds is not None:
            batch["input_embeds"] = jnp.asarray(req.input_embeds[None],
                                                jnp.float32)
        sp = req.params
        first, self.cache, self.state = self._admit_step(
            self.params, batch, self.cache, self._zero_cache1, slot, start,
            self.state, jnp.float32(sp.temperature), jnp.int32(sp.top_k),
            jnp.float32(sp.top_p), sampling.request_key(sp.seed, req.rid),
            jnp.int32(self._eos_of(req)), jnp.int32(self._budget(req)))
        self.active[slot] = True
        self.slot_req[slot] = req
        self.slot_out[slot] = [int(first)]
        self.host_syncs += 1                 # the fused admit syncs `first`
        self.slot_admitted[slot] = self.step_count
        self._count_admission(req)
        if self._guard:                      # admit wrote the slot's cache
            self._refresh_cache_fp()
        self._maybe_retire(slot)

    def _count_admission(self, req: Request) -> None:
        self.occ["queue_wait_ns"] += time.perf_counter_ns() - req.queued_ns
        self.occ["admitted"] += 1

    def _budget(self, req: Request) -> int:
        # token n's producing decode writes its KV at cache offset
        # start + n - 2 (token 1 comes from prefill; the final token's own KV
        # is never written), so n tokens need start + n - 1 <= max_len; clamp
        # the request budget to what its slot can hold
        return max(1, min(req.max_new_tokens,
                          self.max_len - self._start_len(req) + 1))

    def _release_keys(self, slot: int) -> Sequence[bytes]:
        """Content keys for every block the retiring slot fully wrote —
        prompt *and* generated tokens, so a multi-turn follow-up whose
        prompt extends this conversation matches the decode-produced blocks
        too. KV position ``p`` always holds token ``p`` of the full
        sequence, so the chain over ``prompt ++ out`` identifies them."""
        req = self.slot_req[slot]
        bs = self.pool.spec.block_size
        full = int(self.slot_pos[slot]) // bs
        if full == 0:
            return ()
        toks = np.concatenate([np.asarray(req.prompt, np.int32),
                               np.asarray(self.slot_out[slot], np.int32)])
        full = min(full, len(toks) // bs)
        return paged_mod.chain_keys(self._prefix_seed, toks[:full * bs], bs)

    def _free_slot(self, slot: int) -> None:
        """Clear a slot's device flag, host mirrors, and (paged) blocks."""
        if self.paged:
            keys = (self._release_keys(slot)
                    if self.slot_cacheable[slot] else ())
        self.active[slot] = False
        self.state = self._retire(self.state, slot)
        self.slot_req[slot] = None
        self.slot_out[slot] = []
        if self.paged:
            self.pool.release(slot, keys=keys)   # free-on-retire (or cache)
            self.slot_prefill_off[slot] = None
            self.slot_chain[slot] = ()
            self.slot_cacheable[slot] = False
            self._tables_dev = None          # force re-upload of the tables
            if self.validate_pool:
                self.pool.check()            # leaks surface at retire time

    def _retire_slot(self, slot: int, reason: str) -> None:
        req = self.slot_req[slot]
        self.finished[req.rid] = FinishedRequest(
            req.rid, np.asarray(self.slot_out[slot], np.int32),
            self._start_len(req), int(self.slot_admitted[slot]),
            self.step_count, reason, preemptions=req.preempt_count)
        self._free_slot(slot)
        if reason in self.events:
            self.events[reason] += 1

    def _maybe_retire(self, slot: int) -> None:
        req = self.slot_req[slot]
        out = self.slot_out[slot]
        eos = self._eos_of(req)
        if eos >= 0 and out and out[-1] == eos:
            self._retire_slot(slot, "eos")
        elif len(out) >= self._budget(req):
            self._retire_slot(slot, "length")

    def _preempt_slot(self, slot: int) -> Request:
        """Evict a live request: free its slot/blocks, discard its partial
        stream, and return it for requeueing. Replay is bit-identical to an
        uninterrupted run (per-request determinism), so preemption is
        invisible in the stream. Ages the request's effective priority."""
        req = self.slot_req[slot]
        req.preempt_count += 1               # aging: no starvation
        self._free_slot(slot)
        self.events["preemptions"] += 1
        req.queued_ns = time.perf_counter_ns()   # its wait starts again
        return req

    def _eff_priority(self, req: Request) -> int:
        return req.priority + req.preempt_count

    def _next_candidate(self) -> Optional[int]:
        """Queue index of the next request to admit: highest effective
        priority among arrived requests; equal priorities keep FIFO order."""
        best = None
        for i, req in enumerate(self.queue):
            if req.arrival > self.step_count:
                continue                     # trace replay: not yet arrived
            if (best is None or self._eff_priority(req)
                    > self._eff_priority(self.queue[best])):
                best = i
        return best

    def _plan_preemption(self, req: Request, fresh: int,
                         hits: Sequence[int]) -> Optional[List[int]]:
        """Victim slots to evict so `req` can reserve ``fresh`` new blocks
        (on top of attaching the ``hits`` prefix blocks), or None.

        Only strictly-lower-effective-priority slots qualify; victims are
        taken most-recently-admitted first (least progress lost). Pure
        planning — no side effects until the caller commits. A victim's
        blocks that the new request's prefix hits cover are *not* counted as
        gain (`BlockPool.can_admit` pins them right back), and a preempted
        victim's own cached prefix survives in the index, so its replay
        resumes from the shared blocks instead of re-prefilling."""
        pri = self._eff_priority(req)
        victims = sorted(
            (s for s in np.flatnonzero(self.active)
             if self._eff_priority(self.slot_req[s]) < pri),
            key=lambda s: (-int(self.slot_admitted[s]), -s))
        chosen: List[int] = []
        for s in victims:
            if self.pool.can_admit(fresh, hits, exclude=chosen):
                break
            chosen.append(s)
        if not self.pool.can_admit(fresh, hits, exclude=chosen):
            return None
        return chosen

    def _enforce_deadlines(self) -> None:
        """Retire every live/queued request past its step budget (budgets
        are measured from `arrival` in engine steps — deterministic)."""
        for slot in np.flatnonzero(self.active):
            req = self.slot_req[slot]
            age = self.step_count - req.arrival
            if (req.ttft_deadline is not None and not self.slot_out[slot]
                    and age >= req.ttft_deadline):
                self._retire_slot(slot, "deadline_ttft")
            elif req.total_deadline is not None and age >= req.total_deadline:
                self._retire_slot(slot, "deadline_total")
        if any(r.ttft_deadline is not None or r.total_deadline is not None
               for r in self.queue):
            keep = collections.deque()
            for req in self.queue:
                age = self.step_count - req.arrival
                reason = None
                if req.arrival <= self.step_count:
                    if (req.ttft_deadline is not None
                            and age >= req.ttft_deadline):
                        reason = "deadline_ttft"
                    elif (req.total_deadline is not None
                          and age >= req.total_deadline):
                        reason = "deadline_total"
                if reason:
                    self._finish_unstarted(req, reason)
                else:
                    keep.append(req)
            self.queue = keep

    def _admit_ready(self) -> None:
        for slot in range(self.n_slots):
            if not self.queue:
                return
            if self.active[slot]:
                continue
            idx = self._next_candidate()
            if idx is None:
                return                       # nothing has arrived yet
            req = self.queue[idx]
            if self.paged:
                need = self._reserved_blocks(req)
                if need > self.pool.spec.n_blocks:
                    raise ValueError(
                        f"request {req.rid} needs {need} blocks "
                        f"but the pool holds {self.pool.spec.n_blocks} — "
                        "raise n_blocks or lower max_new_tokens")
                # the prefix plan is committed here: preempting victims may
                # surface new cached blocks, but re-matching after eviction
                # could pin more residents than the feasibility check saw
                plan = self._prefix_plan(req)
                keys, hits, extra_cow, resume = plan
                fresh = need - len(hits) + extra_cow
                if not self.pool.can_admit(fresh, hits):
                    victims = self._plan_preemption(req, fresh, hits)
                    if victims is None:
                        return               # out of blocks: backpressure
                    del self.queue[idx]
                    # evicted requests go back to the queue front (oldest
                    # first among themselves); aging already bumped their
                    # effective priority for the next admission pass
                    for s in victims:
                        self.queue.appendleft(self._preempt_slot(s))
                    self._admit_paged(slot, req, plan)
                    continue
                del self.queue[idx]
                self._admit_paged(slot, req, plan)
            else:
                del self.queue[idx]
                self._admit(slot, req)

    def _dev_cached(self, name: str, arr: np.ndarray):
        """Device copy of a small per-step host array, reused while the host
        bytes are unchanged. In the pure-decode steady state ``q_len`` (all
        ones) and ``emit`` (all True) repeat every step, and host->device
        uploads of even tiny arrays are a measurable slice of a small-model
        step — nothing donates its inputs, so reuse is safe."""
        key = (arr.shape, arr.tobytes())
        hit = self._dev_cache.get(name)
        if hit is not None and hit[0] == key:
            return hit[1]
        dev = jax.device_put(arr)
        self._dev_cache[name] = (key, dev)
        return dev

    def _paged_step(self) -> None:
        """One mixed prefill+decode chunk step over all slots."""
        with self._phase("prepare"):
            live = np.flatnonzero(self.active)
            prefilling = [s for s in live
                          if self.slot_prefill_off[s] is not None]
            args, clens, emit = self._chunk_inputs(live, prefilling)
        # dispatch with recovery: params are read at call time (a retry after
        # restore-from-pristine must not replay the poisoned leaves) and
        # nothing below mutates scheduler state, so a retried or quarantined
        # step cannot double-commit (`_chunk_inputs`' pool.ensure is
        # idempotent)
        with self._phase("dispatch"):
            dispatched = self._dispatch(
                lambda: self._chunk(self.params, *args))
            if dispatched is None:           # quarantined: step consumed
                self.step_count += 1
                return
            # the step's inputs, the previous cache and state among them,
            # are freed here, inside the phase
            del args
            tok, self.cache, self.state = dispatched
        with self._phase("sync"):
            tok_np = np.asarray(tok)         # the one per-step device sync
        with self._phase("commit"):
            self._commit_chunk(live, prefilling, clens, emit, tok_np)

    def _chunk_inputs(self, live, prefilling):
        """The chunk step's arguments after the params, the prompt chunk
        length of each prefilling slot, and the emit mask; allocates the
        step's blocks and applies pending copy-on-write clones first."""
        # step width: the widest remaining chunk this step actually needs
        # (bounded by prefill_chunk, so at most prefill_chunk distinct
        # compiled widths) — decode rows in a mixed step pay for the width,
        # so never pad the step beyond the largest live chunk
        t = max((min(self.prefill_chunk,
                     self._start_len(self.slot_req[s])
                     - self.slot_prefill_off[s]) for s in prefilling),
                default=1)
        b = self.n_slots
        q_len = np.zeros(b, np.int32)
        emit = np.zeros(b, bool)
        tokens = np.zeros((b, t), np.int32)
        # VLM embeds ride the step only while some chunk actually covers
        # patch positions — pure-decode steps skip the patch_proj GEMM the
        # embed-select path would otherwise pay every token
        vlm = self.cfg.family == "vlm" and any(
            self.slot_prefill_off[s] is not None
            and self.slot_req[s].input_embeds is not None
            and self.slot_prefill_off[s] < self.slot_req[s].input_embeds.shape[0]
            for s in live)
        embeds = np.zeros((b, t, self.cfg.d_model), np.float32) if vlm else None
        emask = np.zeros((b, t), bool) if vlm else None
        clens = {}
        tables_dirty = self._tables_dev is None
        for s in live:
            req = self.slot_req[s]
            off = self.slot_prefill_off[s]
            if off is not None:              # prompt chunk
                start = self._start_len(req)
                clen = min(t, start - off)
                clens[s] = clen
                q_len[s] = clen
                emit[s] = off + clen == start
                s_img = (req.input_embeds.shape[0]
                         if req.input_embeds is not None else 0)
                for j in range(clen):
                    pos = off + j
                    if pos < s_img:
                        embeds[s, j] = req.input_embeds[pos]
                        emask[s, j] = True
                    else:
                        tokens[s, j] = req.prompt[pos - s_img]
                tables_dirty |= self.pool.ensure(s, off + clen)
            else:                            # decode row
                q_len[s] = 1
                emit[s] = True
                tokens[s, 0] = self.slot_out[s][-1]
                tables_dirty |= self.pool.ensure(s, int(self.slot_pos[s]) + 1)
        self._apply_cow()
        if tables_dirty:
            self._tables_dev = jnp.asarray(self.pool.tables)
        self.cache = dict(self.cache, block_tables=self._tables_dev)
        if prefilling or vlm:
            tok_dev = jax.device_put(tokens)
        else:
            # pure-decode step: every live row's token is the one the device
            # sampled last step (``state["last_tok"]`` — the host mirrors it
            # into slot_out before building ``tokens``), and q_len == 0 rows
            # only ever write to the dump block, so the device copy already
            # holds this step's tokens — skip the upload
            tok_dev = self.state["last_tok"]
        args = [tok_dev, self.cache, self.state,
                self._dev_cached("q_len", q_len),
                self._dev_cached("emit", emit)]
        if vlm:
            args += [jnp.asarray(embeds), jnp.asarray(emask)]
        return args, clens, emit

    def _commit_chunk(self, live, prefilling, clens, emit, tok_np) -> None:
        """Host bookkeeping after a chunk step: counters, outputs, prefix
        publication and retirement."""
        self.host_syncs += 1
        if self._guard:
            self._refresh_cache_fp()
        self.step_count += 1
        if len(prefilling) < len(live):
            self.decode_steps += 1
        b = self.n_slots
        self.occ["slot_steps"] += b
        self.occ["slot_active_steps"] += len(live)
        self.occ["block_steps"] += self.pool.spec.n_blocks
        self.occ["block_alloc_steps"] += self.pool.allocated_blocks
        for s in live:
            if s in clens:
                clen = clens[s]
                self.slot_prefill_off[s] += clen
                self.slot_pos[s] += clen
                self.occ["prefill_tokens"] += clen
                if self.slot_prefill_off[s] == self._start_len(self.slot_req[s]):
                    self.slot_prefill_off[s] = None
                    if self.slot_cacheable[s]:
                        # prompt fully resident: publish its full blocks so
                        # concurrent same-prefix admissions share them now,
                        # not only after this request retires
                        self.pool.publish(s, self.slot_chain[s])
            else:
                self.slot_pos[s] += 1
                self.occ["decode_tokens"] += 1
            if emit[s]:
                self.slot_out[s].append(int(tok_np[s]))
                self._maybe_retire(s)

    def _apply_cow(self) -> None:
        """Apply pending copy-on-write block clones on device (one fused
        call over every pool leaf), then refresh the scrub fingerprint —
        the clone is a legitimate cache rewrite, exactly like admit's slot
        wipe, and must not read as corruption. Because the pool is
        physically shared, the fingerprint covers each block once however
        many tables map it."""
        copies = self.pool.drain_copies()
        if not copies:
            return
        src = jnp.asarray([c[0] for c in copies], jnp.int32)
        dst = jnp.asarray([c[1] for c in copies], jnp.int32)
        self.cache = self._copy_blocks(self.cache, src, dst)
        if self._guard:
            self._refresh_cache_fp()

    def _multi_horizon(self) -> None:
        """One fused ``multi_step``-sub-step decode horizon (single dispatch).

        The device runs ``n`` chained decode sub-steps under ``lax.scan``
        (`steps.make_multi_step`): sampling streams fold per-token inside the
        scan, and the device-resident retirement mask (EOS / budget) freezes
        a slot that finishes mid-horizon so its cache and position stop
        advancing with no host involvement. The host syncs exactly one
        ``(n, B)`` token block per horizon, then replays its per-sub-step
        bookkeeping from it — ``-1`` marks sub-steps on which a slot emitted
        nothing, so trim-past-EOS holds by construction. Admission,
        deadlines, and retirement run at horizon boundaries only
        (``step_count`` advances by ``n``; see docs/serving.md for the
        retirement-lag semantics).
        """
        n = self.multi_step
        live = np.flatnonzero(self.active)
        if self.paged:
            with self._phase("prepare"):
                self._horizon_blocks(live, n)
        # recovery composes unchanged: cache/state are only assigned on
        # success, so a retry replays the whole horizon from the pre-horizon
        # snapshot and the replay is bit-identical
        with self._phase("dispatch"):
            dispatched = self._dispatch(
                lambda: self._multi(self.params, self.cache, self.state))
            if dispatched is None:           # quarantined: horizon consumed
                self.step_count += n
                return
            toks, self.cache, self.state = dispatched
        with self._phase("sync"):
            tok_np = np.asarray(toks)        # the one per-*horizon* sync
        with self._phase("commit"):
            self._commit_horizon(live, n, tok_np)

    def _horizon_blocks(self, live, n: int) -> None:
        """Horizon-aware alloc-on-write: cover the worst case (all ``n``
        sub-steps live) up front; ensure_horizon clamps to the admit-time
        reservation, which the device-side budget mask provably never writes
        past."""
        tables_dirty = self._tables_dev is None
        for s in live:
            tables_dirty |= self.pool.ensure_horizon(
                s, int(self.slot_pos[s]) + n)
        self._apply_cow()
        if tables_dirty:
            self._tables_dev = jnp.asarray(self.pool.tables)
        self.cache = dict(self.cache, block_tables=self._tables_dev)

    def _commit_horizon(self, live, n: int, tok_np) -> None:
        """Host bookkeeping after a horizon, replayed per sub-step from the
        ``(n, B)`` token block."""
        self.host_syncs += 1
        if self._guard:
            self._refresh_cache_fp()
        self.step_count += n
        b = self.n_slots
        for j in range(n):
            live_j = int((tok_np[j] >= 0).sum())
            if live_j:
                self.decode_steps += 1
            if self.paged:
                self.occ["slot_steps"] += b
                self.occ["slot_active_steps"] += live_j
                self.occ["block_steps"] += self.pool.spec.n_blocks
                self.occ["block_alloc_steps"] += self.pool.allocated_blocks
                self.occ["decode_tokens"] += live_j
        for s in live:
            emitted = tok_np[:, s]
            emitted = emitted[emitted >= 0]
            self.slot_out[s].extend(int(t) for t in emitted)
            if self.paged:
                self.slot_pos[s] += len(emitted)
            self._maybe_retire(s)

    # --- fault detection & recovery (policy.guard != "none") ----------------

    def _scrub_view(self):
        """The cache leaves the integrity scrub covers. `block_tables` is
        host-authoritative (re-pushed every step) and excluded."""
        if isinstance(self.cache, dict):
            return {k: v for k, v in self.cache.items()
                    if k != "block_tables"}
        return self.cache

    def _scrub(self) -> None:
        """Bit-level integrity sweep before a step: bound params against the
        init-time fingerprints, KV cache against the post-commit
        fingerprints, device tables against host golden rebuilds. Raises
        ``AbftFaultError`` naming the corrupted leaves."""
        bad = [("params", p) for p in
               abft.verify_fingerprint(self.params, self._params_fp)]
        if self._cache_fp is not None:
            bad += [("cache", p) for p in
                    abft.verify_fingerprint(self._scrub_view(),
                                            self._cache_fp)]
        if bad:
            raise abft.AbftFaultError(
                [abft.Fault(f"{dom}:{path}", "memory", 1.0, 0.0)
                 for dom, path in bad])
        backends = ({self.policy.backend}
                    | set((self.policy.overrides or {}).values()))
        for be in sorted(backends):
            abft.verify_tables(self.policy, be, layer="<serve>")

    def _restore_known_good(self, kinds) -> None:
        """Swap the (possibly poisoned) params back to the pristine init
        reference; a table fault additionally clears the device table caches
        so the next trace re-uploads from the host golden copies."""
        self.params = self._pristine_params
        self._params_fp = abft.tree_fingerprint(self.params)
        if "table" in kinds:
            from repro.core import emulate, error_delta
            for fn in (emulate.product_table_jnp,
                       error_delta.factor_tables_jnp):
                # an active fault-injection patch is a plain function
                if hasattr(fn, "cache_clear"):
                    fn.cache_clear()

    def _quarantine(self) -> None:
        """KV corruption recovery: requeue every active request (replay from
        the prompt is bit-identical, so the corruption never reaches a
        stream) and rebuild the block pool and paged cache from scratch."""
        self.events["quarantines"] += 1
        # invalidate the prefix index FIRST and mark every live slot
        # non-cacheable: the preemption releases below must not (re)index
        # blocks whose contents are suspect — a corrupted shared block
        # served to a later same-prefix request would defeat the whole
        # quarantine. Requeued victims re-prefill cold against the fresh
        # pool's empty index.
        self.pool.invalidate()
        self.prefix_events["prefix_invalidations"] += 1
        order = sorted(np.flatnonzero(self.active),
                       key=lambda s: (-int(self.slot_admitted[s]), -s))
        for s in order:
            self.slot_cacheable[s] = False
            self.queue.appendleft(self._preempt_slot(s))
        spec = self.pool.spec
        self.pool = paged_mod.BlockPool(spec, self.n_slots, self.max_len)
        self.cache = self.model.init_paged_cache(
            self.n_slots, self.max_len, spec.n_blocks, spec.block_size)
        self._tables_dev = None
        self._refresh_cache_fp()

    def _refresh_cache_fp(self) -> None:
        """Fingerprint the KV cache again after a legitimate rewrite."""
        with self._phase("scrub"):
            self._cache_fp = abft.tree_fingerprint(self._scrub_view())

    def _backoff_wait(self, attempts: int) -> None:
        """Retry backoff as a monotonic-deadline wait.

        The old implementation blocked in one uncapped ``time.sleep`` — a
        high attempt count (or a large ``retry_backoff_s``) could stall the
        scheduler far past the step budget. The wait is now capped by
        ``retry_backoff_cap_s``, sleeps in short slices against a
        ``time.monotonic`` deadline (immune to wall-clock jumps), and the
        time actually spent is the ``backoff`` phase (``occ["backoff_ns"]``,
        ``stats["backoff_s_total"]``).
        """
        if not self.retry_backoff_s:
            return
        want = self.retry_backoff_s * attempts
        if self.retry_backoff_cap_s is not None:
            want = min(want, self.retry_backoff_cap_s)
        with self._phase("backoff"):
            deadline = time.monotonic() + want
            remaining = want
            while remaining > 0:
                time.sleep(min(remaining, 0.02))
                remaining = deadline - time.monotonic()

    def _dispatch(self, step_fn):
        """Run one jitted step under the recovery protocol.

        * ``TransientError`` (preemption notice, flaky interconnect — or the
          fault injector) -> bounded retry with linear backoff.
        * ABFT fault in **params / weights / tables** -> restore from the
          pristine snapshot and re-dispatch (bounded by `max_step_retries`).
        * ABFT fault in the **KV cache** -> quarantine: requeue all active
          requests, rebuild pool + cache; returns None (step consumed).
        * The contiguous engine fails fast on any ABFT fault (its fused
          admit emits tokens inside jit, so there is no safe replay point).

        Exhausted retries re-raise to the caller.
        """
        attempts = 0
        while True:
            try:
                if self._guard:
                    with self._phase("scrub"):
                        self._scrub()
                out = step_fn()
                if self._guard:
                    with self._phase("sync"):
                        jax.block_until_ready(out)
                    faults = abft.drain_faults()
                    if faults:
                        raise abft.AbftFaultError(faults)
                return out
            except TransientError:
                attempts += 1
                self.events["step_retries"] += 1
                if attempts > self.max_step_retries:
                    raise
                self._backoff_wait(attempts)
            except abft.AbftFaultError as e:
                self.events["faults_detected"] += len(e.faults)
                if not self.paged:
                    raise                    # contiguous: fail fast
                if any(f.kind == "memory" and f.layer.startswith("cache:")
                       for f in e.faults):
                    self._quarantine()
                    return None
                attempts += 1
                self.events["step_retries"] += 1
                if attempts > self.max_step_retries:
                    raise
                self._restore_known_good({f.kind for f in e.faults})
                self._backoff_wait(attempts)

    def step(self) -> None:
        """Enforce deadlines, admit what fits, run one batched ragged step."""
        # cache scrub FIRST: admission legitimately rewrites a slot's cache
        # and refreshes the fingerprint, so corruption struck between steps
        # must be caught before any admit can launder it into the baseline
        if self._guard and self._cache_fp is not None:
            with self._phase("scrub"):
                bad = abft.verify_fingerprint(self._scrub_view(),
                                              self._cache_fp)
                if bad:
                    self.events["faults_detected"] += len(bad)
                    if not self.paged:       # contiguous: fail fast
                        raise abft.AbftFaultError(
                            [abft.Fault(f"cache:{p}", "memory", 1.0, 0.0)
                             for p in bad])
                    self._quarantine()
                    self.step_count += 1     # step consumed by recovery
                    return
        with self._phase("admit"):
            self._enforce_deadlines()
            self._admit_ready()
            self.peak_active = max(self.peak_active, int(self.active.sum()))
            idle = not self.active.any()
        if idle:
            self.step_count += 1             # idle tick (waiting on arrivals)
            return
        if self.paged:
            # fused horizons only apply while every live slot is decoding:
            # a prefilling slot needs per-chunk host orchestration, and
            # falling back to the per-step path keeps streams bit-identical
            # (token values are batch-composition independent)
            if self.multi_step > 1 and all(
                    self.slot_prefill_off[s] is None
                    for s in np.flatnonzero(self.active)):
                self._multi_horizon()
            else:
                self._paged_step()
            return
        if self.multi_step > 1:
            self._multi_horizon()
            return
        with self._phase("dispatch"):
            next_tok, self.cache, self.state = self._dispatch(
                lambda: self._decode(self.params, self.cache, self.state))
        with self._phase("sync"):
            next_np = np.asarray(next_tok)   # the one per-step device sync
        with self._phase("commit"):
            self.host_syncs += 1
            if self._guard:
                self._refresh_cache_fp()
            self.step_count += 1
            self.decode_steps += 1
            for slot in np.flatnonzero(self.active):
                self.slot_out[slot].append(int(next_np[slot]))
                self._maybe_retire(slot)

    def run(self, requests: Sequence[Request] = (),
            max_steps: Optional[int] = None) -> Dict[int, FinishedRequest]:
        """Drive the engine until every submitted request has finished."""
        for req in sorted(requests, key=lambda r: (r.arrival, r.rid)):
            self.submit(req)
        limit = max_steps if max_steps is not None else 10 ** 9
        while (self.queue or self.active.any()) and self.step_count < limit:
            self.step()
        return dict(self.finished)

    @property
    def stats(self) -> Dict[str, Any]:
        gen = sum(len(f.tokens) for f in self.finished.values())
        occ = self.occ
        out: Dict[str, Any] = {
            "steps": self.step_count, "decode_steps": self.decode_steps,
            "generated_tokens": gen, "finished": len(self.finished),
            "peak_active_slots": self.peak_active,
            # host-overhead telemetry: device->host token syncs (admit +
            # per-step / per-horizon blocks) and measured retry backoff
            "multi_step": self.multi_step,
            "host_syncs": self.host_syncs,
            "syncs_per_token": round(self.host_syncs / max(1, gen), 4),
            "backoff_s_total": round(occ["backoff_ns"] * 1e-9, 6)}
        # time in each step phase (`spans`) and the mean wait in the queue
        # from submit to admission
        out.update({f"{p}_ms": round(occ[spans.counter(p)] * 1e-6, 3)
                    for p in spans.PHASES})
        out["queue_wait_mean_ms"] = round(
            occ["queue_wait_ns"] * 1e-6 / max(1, occ["admitted"]), 3)
        out.update(self.events)              # reliability counters
        # bound approx_delta GEMM call sites traced in this process, per
        # lookup form of the moving operand's factor (select / gather)
        out["delta_forms"] = dict(error_delta.FORM_SITES)
        if self.paged:
            out.update({
                # occupancy: fraction of slot-steps / pool-block-steps that
                # held live work, plus the prefill-vs-decode token split
                "slot_utilization": round(occ["slot_active_steps"]
                                          / max(1, occ["slot_steps"]), 3),
                "block_utilization": round(occ["block_alloc_steps"]
                                           / max(1, occ["block_steps"]), 3),
                "peak_allocated_blocks": self.pool.peak_allocated,
                "prefill_tokens": occ["prefill_tokens"],
                "decode_tokens": occ["decode_tokens"],
                # prefix-cache counters: engine-side hit accounting plus the
                # pool's sharing/COW/eviction totals (pool counters reset on
                # a quarantine rebuild; hit counters are cumulative)
                "prefix_cache": self.prefix_cache,
                "prefix_shared_blocks": self.pool.shared_attached,
                "prefix_cow_copies": self.pool.cow_copies,
                "prefix_evicted_blocks": self.pool.evicted_blocks,
                "prefix_cached_blocks": self.pool.cached_blocks,
            })
            out.update(self.prefix_events)
        return out


def make_poisson_trace(n_requests: int, *, rate: float, vocab_size: int,
                       prompt_lens: Sequence[int] = (8, 12, 16),
                       gen_lens: Sequence[int] = (4, 8, 12, 16, 24),
                       seed: int = 0,
                       params: sampling.SamplingParams = sampling.GREEDY
                       ) -> List[Request]:
    """Synthetic ragged request trace with Poisson arrivals.

    Inter-arrival gaps are exponential with mean `1/rate` (in engine decode
    steps); prompt and generation lengths are drawn uniformly from the given
    pools — the raggedness a padded lockstep loop pays for and continuous
    batching absorbs.
    """
    rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    for rid in range(n_requests):
        t += rng.exponential(1.0 / rate)
        plen = int(rng.choice(prompt_lens))
        out.append(Request(
            rid=rid,
            prompt=rng.integers(0, vocab_size, plen).astype(np.int32),
            max_new_tokens=int(rng.choice(gen_lens)),
            params=params,
            arrival=int(t)))
    return out


def elapsed(fn):
    """(result, seconds) of `fn()` — tiny helper for bench instrumentation."""
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0
