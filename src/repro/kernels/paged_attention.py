"""Paged flash-attention / flash-decoding as a Pallas TPU kernel (forward).

The gather path in `models.layers.chunked_attention` reconstructs a
contiguous KV layout in HBM before the flash scan: each KV chunk does a
`jnp.take` through the block table, materializing chunk-sized K/V copies the
scan immediately consumes. This kernel removes the round trip — the block
table rides in as a *scalar-prefetch* operand (`pltpu.PrefetchScalarGridSpec`)
so the KV inner loop DMAs pool blocks straight into VMEM through the table:
storage stays paged end to end, exactly the operand-resident dataflow the
paper's systolic PEs are built around.

Two schedules share one kernel body:

* ``n_splits=1`` (default) — one grid cell owns a (batch row, Q chunk) pair
  (every KV head of the row is handled inside the cell, one 2-D dot per
  head) and scans every KV chunk sequentially. The math mirrors
  `chunked_attention`'s ``kv_body`` operation for operation (same chunk grid,
  same masking, same online-softmax update, same reduction order), so the
  output is **bit-identical** to the gather path — and therefore to solo
  lockstep decode — on every backend. This is the serving configuration.
* ``n_splits>1`` — flash-decoding: the KV chunk range is split across grid
  cells that each produce a partial softmax ``(acc, m, l)``; partials are
  combined outside the kernel with the standard log-sum-exp merge. The
  combine reassociates the softmax sums, so parity with the sequential scan
  is up to float rounding (~1e-6), not bitwise — long-context throughput at
  the cost of the strict determinism contract.

Unlike the gather path (a fixed-trip `lax.scan` over every table chunk), the
KV loop bound here is *dynamic per batch row*: chunks past
``ceil(kv_valid_len / chunk)`` (and, causally, past the row's last query
position) are never visited. Skipped chunks are fully masked in the
reference — an exact bitwise no-op (``corr = exp(0)``, ``p = exp(-inf)``) —
so early exit is free, and decode work scales with each slot's *live* length
instead of the table width. Rows with ``kv_valid_len == 0`` return zeros
(the reference emits a masked-garbage mean over V; no caller reads either).

Pool payloads may be int8 (`layers.cache_store`) or bf16. Mosaic DMAs only
whole (8, 128) tiles, which a block's ``(KH, D)`` minor dims are not, so the
wrapper hands the kernel a head-major, lane-padded float32 copy of each pool
(`_lane_pool`; exact for both payloads) and the kernel dequantizes after the
load. That copy is made on every call.

Written for Mosaic; validated in interpret mode against the gather path
(tests/test_paged.py) like the other kernels in this package.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ops

BIG_NEG = -2.3819763e38  # min bf16 (matches layers.BIG_NEG)


def tpu_contract(*, batch: int, q_len: int, kv_heads: int, q_per_kv: int,
                 head_dim: int, n_pool: int, block_size: int,
                 table_width: int, chunk: int = 1024, q_chunk: int = 1024,
                 n_splits: int = 1, kv_dtype: str = "float32",
                 q_dtype: str = "float32"):
    """Static lowering contract mirroring `paged_attention`'s pallas_call.

    Shape/dtype geometry only (no tracing, no jax). Mirrors the wrapper's
    chunk narrowing / table+query padding arithmetic exactly, so
    `autotune.paged_kernel_plan` can pre-prune (kv_chunk, n_splits) plans
    that cannot lower. The pools ride in ANY space and are DMA-staged chunk
    by chunk, so VMEM scales with ``chunk`` and not with ``n_pool``.
    """
    from repro.analysis import contracts as C
    b, kh, g, d = batch, kv_heads, q_per_kv, head_dim
    skv = table_width * block_size
    chunk = min(chunk, skv)
    if chunk % block_size:
        raise ValueError(f"attention chunk {chunk} must be a multiple of "
                         f"the KV block size {block_size}")
    nbpc = chunk // block_size
    nk = -(-skv // chunk)
    n_splits = max(1, min(int(n_splits), nk))
    width_p = nk * nbpc                     # table padded with the dump row
    qc = min(q_chunk, q_len)
    nq = -(-q_len // qc)
    rows = g * qc
    dp = -(-d // 128) * 128                 # pools are lane-padded (_lane_pool)
    pool_shape = (n_pool, kh, block_size, dp)
    cell = lambda bi, qi, si, *_: (bi, qi, 0, 0, 0)
    operands = [
        C.OperandSpec("q", (b, nq, kh, rows, d), q_dtype,
                      (1, 1, kh, rows, d), cell),
        C.OperandSpec("q_positions", (b, nq, rows, 1), "int32",
                      (1, 1, rows, 1), lambda bi, qi, si, *_: (bi, qi, 0, 0)),
        C.OperandSpec("k_pool", pool_shape, "float32", memory_space="any"),
        C.OperandSpec("v_pool", pool_shape, "float32", memory_space="any"),
    ]
    if n_splits == 1:
        operands.append(C.OperandSpec(
            "o", (b, nq, kh, rows, d), q_dtype, (1, 1, kh, rows, d), cell))
    else:
        s_map = lambda bi, qi, si, *_: (bi, qi, si, 0, 0, 0)
        operands += [
            C.OperandSpec("acc", (b, nq, n_splits, kh, rows, d), "float32",
                          (1, 1, 1, kh, rows, d), s_map),
            C.OperandSpec("m", (b, nq, n_splits, kh, rows, 1), "float32",
                          (1, 1, 1, kh, rows, 1), s_map),
            C.OperandSpec("l", (b, nq, n_splits, kh, rows, 1), "float32",
                          (1, 1, 1, kh, rows, 1), s_map),
        ]
    return C.KernelGeometry(
        kernel="kernels.paged_attention.paged_attention",
        grid=(b, nq, n_splits),
        operands=tuple(operands),
        scalar_prefetch=(
            C.ScalarSpec("block_tables", (b, width_p), "int32"),
            C.ScalarSpec("kv_valid_len", (b,), "int32"),
            C.ScalarSpec("q_max", (b, nq), "int32"),
            C.ScalarSpec("window", (1,), "int32"),
        ),
        scratch_bytes=C.scratch_bytes(
            ((nbpc, kh, block_size, dp), "float32"),
            ((nbpc, kh, block_size, dp), "float32")),
        tag=(f"b{b}q{q_len}kh{kh}g{g}d{d}pool{n_pool}x{block_size}"
             f"w{table_width}c{chunk}s{n_splits}{kv_dtype}"),
    )


def _kernel(tables_ref, kvlen_ref, qmax_ref, win_ref,      # scalar prefetch
            q_ref, qpos_ref, k_ref, v_ref, *rest,
            kh: int, d: int, chunk: int, blk_sz: int, nk: int, n_splits: int,
            causal: bool, softcap: float, int8_scale: float, quant: bool):
    # rest = out refs (1 or 3 depending on n_splits) + VMEM staging scratch
    # for one K chunk and one V chunk + the DMA semaphore
    out_refs, (k_scr, v_scr, dma_sem) = rest[:-3], rest[-3:]
    b = pl.program_id(0)
    qi = pl.program_id(1)
    si = pl.program_id(2)
    nbpc = chunk // blk_sz
    rows = q_ref.shape[-2]                          # G * qc query rows per head

    qs = [q_ref[0, 0, h].astype(jnp.float32) for h in range(kh)]  # (rows, D)
    qpos = qpos_ref[0, 0]                                          # (rows, 1)
    kvl = kvlen_ref[b]
    win = win_ref[0]
    window_eff = jnp.where(win > 0, win,
                           jnp.iinfo(jnp.int32).max).astype(jnp.int32)

    def stage(ref, scr, ci):
        # in-kernel table walk: one pool-block DMA per table entry, HBM (ANY
        # space) -> VMEM scratch — the chunk's contiguous layout is assembled
        # in VMEM, never in HBM, and the pools themselves are never blocked
        # into VMEM (a pool is 10-100x the VMEM budget at production sizes)
        copies = []
        for j in range(nbpc):
            blk = tables_ref[b, ci * nbpc + j]
            cp = pltpu.make_async_copy(ref.at[blk], scr.at[j], dma_sem)
            cp.start()
            copies.append(cp)
        for cp in copies:
            cp.wait()

    def head(scr, h):
        # (nbpc, blk_sz, D_pad) -> (chunk, D): identical element order to
        # concatenating the per-block loads, so bits match the gather path
        x = scr[:, h].reshape(chunk, scr.shape[-1])[:, :d]
        return x / int8_scale if quant else x

    def body(ci, state):
        stage(k_ref, k_scr, ci)
        stage(v_ref, v_scr, ci)
        kpos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32,
                                                     (rows, chunk), 1)
        valid = kpos < kvl
        if causal:
            delta = qpos - kpos
            valid = valid & (delta >= 0) & (delta < window_eff)
        new = []
        for h in range(kh):
            # one (rows, D) x (chunk, D) contraction per KV head: the same
            # per-(b, kh) products as the reference's batched einsum
            acc, m, l = state[h]
            s = jax.lax.dot_general(qs[h], head(k_scr, h),
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(valid, s, BIG_NEG)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1, keepdims=True)
            acc_new = acc * corr + jax.lax.dot_general(
                p, head(v_scr, h), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            new.append((acc_new, m_new, l_new))
        return tuple(new)

    init = tuple((jnp.zeros((rows, d), jnp.float32),
                  jnp.full((rows, 1), BIG_NEG, jnp.float32),
                  jnp.zeros((rows, 1), jnp.float32)) for _ in range(kh))
    cps = -(-nk // n_splits)                     # chunks per split
    lo = si * cps
    hi = jnp.minimum(lo + cps, nk)
    # dynamic per-row early exit: chunks past the live KV length (and, for
    # causal attention, past the block's last query position) are exact
    # bitwise no-ops in the reference scan — skip them
    hi = jnp.minimum(hi, (kvl + chunk - 1) // chunk)
    if causal:
        hi = jnp.minimum(hi, (qmax_ref[b, qi] + chunk) // chunk)
    state = jax.lax.fori_loop(lo, hi, body, init)

    for h, (acc, m, l) in enumerate(state):
        if n_splits == 1:
            o_ref, = out_refs
            o_ref[0, 0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        else:
            acc_ref, m_ref, l_ref = out_refs
            acc_ref[0, 0, 0, h] = acc
            m_ref[0, 0, 0, h] = m
            l_ref[0, 0, 0, h] = l


def _lane_pool(pool, lane: int = 128):
    """``(n, blk, KH, D)`` pool -> ``(n, KH, blk, D_pad)`` float32, D padded
    to the lane width: Mosaic DMAs only whole (8, 128) tiles, and the head-
    major order lets the kernel read one head's chunk as a 2-D tile stack.
    The conversion is exact (int8 and bf16 payloads are f32-representable)."""
    d = pool.shape[-1]
    x = pool.astype(jnp.float32).transpose(0, 2, 1, 3)
    return jnp.pad(x, ((0, 0),) * 3 + ((0, -d % lane),))


def paged_attention(q, k_pool, v_pool, block_tables, kv_valid_len,
                    q_positions, *, causal: bool = True, window=0,
                    softcap: float = 0.0, chunk: int = 64,
                    q_chunk: int = 1024, n_splits: int = 1,
                    int8_scale: float = 32.0):
    """Fused paged attention over a shared block pool.

    q: (B, Sq, H, D) — *unscaled* queries (the kernel applies D**-0.5 in the
    query dtype, exactly like `chunked_attention`). k_pool/v_pool:
    ``(n_blocks + 1, block_size, KH, D)`` shared pools, float or int8
    payload (int8 is dequantized in-kernel with ``int8_scale``).
    block_tables: (B, max_blocks) int32 per-slot map, dump row = pool row
    ``n_blocks`` for unused entries. kv_valid_len: scalar or (B,);
    q_positions: (Sq,) or (B, Sq). ``window`` may be a traced per-layer
    scalar (it rides the layer scan); 0/negative disables windowing.
    ``n_splits > 1`` enables flash-decoding (see module docstring — parity
    becomes tolerance-level, not bitwise). Returns (B, Sq, H, D) in q.dtype.
    """
    b, sq, h, d = q.shape
    n_pool, blk_sz, kh, _ = k_pool.shape
    g = h // kh
    width = block_tables.shape[1]
    skv = width * blk_sz
    # Narrow the chunk grid to the logical cache. When the table fits one
    # chunk the reference also runs a single (zero-padded) chunk pass, and a
    # single narrow pass is bitwise-identical to a single wide one — every
    # extra reference column is masked to an exact-zero contribution. The
    # serving win: a 64-token table scans 64 wide, not attn_chunk (1024)
    # wide. (Never changes the chunk *count*, so multi-chunk grids still
    # match the reference exactly.)
    chunk = min(chunk, skv)
    if chunk % blk_sz:
        raise ValueError(f"attention chunk {chunk} must be a multiple of "
                         f"the KV block size {blk_sz}")
    nbpc = chunk // blk_sz
    nk = -(-skv // chunk)
    n_splits = max(1, min(int(n_splits), nk))
    pad_b = nk * nbpc - width
    bt = block_tables.astype(jnp.int32)
    if pad_b:       # pad with the dump row — masked exactly like zero-pad
        bt = jnp.pad(bt, ((0, 0), (0, pad_b)), constant_values=n_pool - 1)

    qc = min(q_chunk, sq)
    nq = -(-sq // qc)
    qpad = nq * qc - sq
    rows = g * qc
    scale = d ** -0.5
    qh = (q * scale).reshape(b, sq, kh, g, d).transpose(0, 2, 3, 1, 4)
    qpos = jnp.asarray(q_positions, jnp.int32)
    qpos = jnp.broadcast_to(qpos[None] if qpos.ndim == 1 else qpos, (b, sq))
    if qpad:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, 0), (0, qpad), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, qpad)))
    # (B, NQ, KH, G*qc, D): one grid cell per (batch row, Q chunk); inside
    # it every KV head is one 2-D (G*qc, D) tile
    q_in = (qh.reshape(b, kh, g, nq, qc, d).transpose(0, 3, 1, 2, 4, 5)
            .reshape(b, nq, kh, rows, d))
    qpos = qpos.reshape(b, nq, 1, qc)
    q_max = qpos.max(axis=(2, 3))                        # (B, NQ) -> SMEM
    qpos_rows = jnp.broadcast_to(qpos, (b, nq, g, qc)).reshape(b, nq, rows, 1)

    kvl = jnp.broadcast_to(
        jnp.asarray(kv_valid_len, jnp.int32).reshape(-1), (b,))
    win = jnp.asarray(window, jnp.int32).reshape(-1)[:1]

    interpret = not ops.on_tpu()
    k_lane, v_lane = _lane_pool(k_pool), _lane_pool(v_pool)
    kern = functools.partial(
        _kernel, kh=kh, d=d, chunk=chunk, blk_sz=blk_sz, nk=nk,
        n_splits=n_splits, causal=causal, softcap=float(softcap),
        int8_scale=float(int8_scale), quant=k_pool.dtype == jnp.int8)
    # pools stay in ANY space (HBM): the kernel DMAs table blocks into the
    # chunk-sized VMEM scratch itself, so the VMEM footprint is O(chunk) and
    # independent of the pool size — blocking a whole pool into VMEM cannot
    # lower at production pool sizes (the kernel auditor pins this)
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    cell = lambda bi, qi, si, *_: (bi, qi, 0, 0, 0)
    split = lambda bi, qi, si, *_: (bi, qi, si, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, nq, n_splits),
        in_specs=[
            pl.BlockSpec((1, 1, kh, rows, d), cell),
            pl.BlockSpec((1, 1, rows, 1), lambda bi, qi, si, *_: (bi, qi, 0, 0)),
            pool_spec,
            pool_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((nbpc,) + k_lane.shape[1:], jnp.float32),
            pltpu.VMEM((nbpc,) + v_lane.shape[1:], jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        out_specs=(
            pl.BlockSpec((1, 1, kh, rows, d), cell)
            if n_splits == 1 else [
                pl.BlockSpec((1, 1, 1, kh, rows, d), split),
                pl.BlockSpec((1, 1, 1, kh, rows, 1), split),
                pl.BlockSpec((1, 1, 1, kh, rows, 1), split),
            ]),
    )
    out_shape = (
        jax.ShapeDtypeStruct((b, nq, kh, rows, d), q.dtype)
        if n_splits == 1 else [
            jax.ShapeDtypeStruct((b, nq, n_splits, kh, rows, d), jnp.float32),
            jax.ShapeDtypeStruct((b, nq, n_splits, kh, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, nq, n_splits, kh, rows, 1), jnp.float32),
        ])
    res = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(bt, kvl, q_max, win, q_in, qpos_rows, k_lane, v_lane)

    if n_splits == 1:
        out = res.reshape(b, nq, kh, g, qc, d)
    else:
        acc, m, l = res                      # (B, NQ, NS, KH, G*qc[, D|1])
        acc = acc.reshape(b, nq, n_splits, kh, g, qc, d)
        m = m.reshape(b, nq, n_splits, kh, g, qc)
        l = l.reshape(b, nq, n_splits, kh, g, qc)
        m_tot = m.max(axis=2)
        w = jnp.exp(m - m_tot[:, :, None])
        l_tot = (l * w).sum(axis=2)
        acc_tot = (acc * w[..., None]).sum(axis=2)
        out = (acc_tot / jnp.maximum(l_tot, 1e-30)[..., None]).astype(q.dtype)
    out = (out.transpose(0, 1, 4, 2, 3, 5)   # (B, NQ, qc, KH, G, D)
           .reshape(b, nq * qc, h, d))
    return out[:, :sq]
