"""Plain reference of a dense GQA decoder (Llama / Qwen2 family) whose every
matrix product runs on the paper's integer PE.

Written from the published description and the paper, in straightforward
``jax.numpy``; it imports nothing of the program under test. The arithmetic
is the configuration's own: bfloat16 activations between layers; each
matrix product quantizes its input rows and the weight's output channels
symmetrically to ``n_bits`` (scale = max|x| * (1 / qmax)), multiplies the
integers on the PE, and dequantizes with the product of the two scales.
The PE is exact, or the approximate PE of ``k`` columns, whose error is
added from its product table (``reference.pe``) with one-hot integer
matmuls; nothing here uses a factorisation of that table.

Departures from the published Llama/Qwen2 description, each shared with the
program it checks (PERF.md lists them): the token embedding is scaled by
sqrt(d_model), and each RMSNorm gain is stored as ``1 + w``.

Float matmuls (attention) run at ``Precision.HIGHEST``; queries are taken
in blocks so a long sequence fits.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import pe

BF16 = jnp.bfloat16
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
GEMM_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w3", "w2")


@dataclasses.dataclass(frozen=True)
class Shape:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    tied: bool
    qkv_bias: bool


@dataclasses.dataclass(frozen=True)
class Arith:
    """The PE the matrix products run on: operand width and approximate
    columns (k = 0 is the exact PE)."""
    n_bits: int = 8
    k: int = 0


def quantize(x, axis: int, n_bits: int):
    qmax = (1 << (n_bits - 1)) - 1
    x = x.astype(F32)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) * np.float32(1.0 / qmax)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int8)
    return q, scale


def prepare(weights, shape: Shape, arith: Arith):
    """Quantize every weight matrix per output channel, once."""
    def q(w):
        return quantize(w, -2, arith.n_bits)
    layers = weights["layers"]
    out = {"embed": weights["embed"], "final_norm": weights["final_norm"],
           "ln1": layers["ln1"], "ln2": layers["ln2"]}
    for name in GEMM_LEAVES:
        out[name] = q(layers[name])
    for name in ("bq", "bk", "bv"):
        if name in layers:
            out[name] = layers[name]
    head = (weights["embed"].T if shape.tied else weights["lm_head"])
    out["head"] = q(head)
    return out


def _int_dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def pe_matmul(xq, wq, arith: Arith):
    """Integer product of (M, K) and (K, N) int8 operands on the PE."""
    acc = _int_dot(xq, wq)
    if arith.k:
        tile = pe.error_tile(8, arith.k)
        mask = (1 << arith.k) - 1
        lo_a = xq.astype(jnp.int32) & mask
        lo_b = wq.astype(jnp.int32) & mask
        for i in range(1, 1 << arith.k):
            row = tile[i]
            if not row.any():
                continue
            w_i = sum(int(row[j]) * (lo_b == j).astype(jnp.int32)
                      for j in range(1 << arith.k) if row[j])
            acc = acc + _int_dot((lo_a == i).astype(jnp.int8),
                                 w_i.astype(jnp.int8))
    return acc


def gemm(x, w, arith: Arith):
    """bf16 (M, K) activations times a quantized weight -> f32 (M, N)."""
    wq, ws = w
    xq, xs = quantize(x, -1, arith.n_bits)
    acc = pe_matmul(xq, wq, arith)
    return acc.astype(F32) * (xs * ws)


def rms_norm(x, w, eps):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(F32))).astype(BF16)


def rope(x, pos, theta):
    """x (S, H, D) bf16, pos (S,) int: rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(BF16)


def attention(q, k, v, q_block: int):
    """Causal GQA softmax attention. q (S, H, D), k/v (S, KH, D) bf16."""
    s, h, d = q.shape
    q_block = min(q_block, s)
    kh = k.shape[1]
    g = h // kh
    qs = q * jnp.asarray(d ** -0.5, BF16)
    qs = qs.reshape(s // q_block, q_block, kh, g, d)
    kf, vf = k.astype(F32), v.astype(F32)
    kpos = jnp.arange(s)

    def block(args):
        qb, start = args
        sc = jnp.einsum("qkgd,ckd->kgqc", qb.astype(F32), kf, precision=HIGHEST)
        qpos = start + jnp.arange(q_block)
        sc = jnp.where((kpos[None, :] <= qpos[:, None])[None, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("kgqc,ckd->qkgd", p, vf, precision=HIGHEST)
        return o.reshape(q_block, h, d).astype(BF16)

    starts = jnp.arange(0, s, q_block)
    return jax.lax.map(block, (qs, starts)).reshape(s, h, d)


@functools.partial(jax.jit, static_argnames=("shape", "arith", "q_block"))
def logits_at(qw, tokens, read, *, shape: Shape, arith: Arith,
              q_block: int = 256):
    """Logits (f32) after the tokens at positions ``read`` of one sequence.

    ``tokens`` (S,) may be padded at the end: attention is causal, so
    padding never reaches an earlier position."""
    s = tokens.shape[0]
    d, h, kh, hd = shape.d_model, shape.n_heads, shape.n_kv_heads, shape.head_dim
    pos = jnp.arange(s)
    x = qw["embed"][tokens].astype(BF16) * jnp.asarray(d ** 0.5, BF16)

    def layer(x, lw):
        hh = rms_norm(x, lw["ln1"], shape.norm_eps)
        q = gemm(hh, lw["wq"], arith).astype(BF16)
        k = gemm(hh, lw["wk"], arith).astype(BF16)
        v = gemm(hh, lw["wv"], arith).astype(BF16)
        if shape.qkv_bias:
            q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
        q = rope(q.reshape(s, h, hd), pos, shape.rope_theta)
        k = rope(k.reshape(s, kh, hd), pos, shape.rope_theta)
        o = attention(q, k, v.reshape(s, kh, hd), q_block)
        x = x + gemm(o.reshape(s, h * hd), lw["wo"], arith).astype(BF16)
        hh = rms_norm(x, lw["ln2"], shape.norm_eps)
        h1 = gemm(hh, lw["w1"], arith).astype(BF16)
        h3 = gemm(hh, lw["w3"], arith).astype(BF16)
        x = x + gemm(jax.nn.silu(h1) * h3, lw["w2"], arith).astype(BF16)
        return x, None

    keys = ["ln1", "ln2", *GEMM_LEAVES] + (["bq", "bk", "bv"]
                                           if shape.qkv_bias else [])
    x, _ = jax.lax.scan(layer, x, {k_: qw[k_] for k_ in keys})
    hid = rms_norm(x[read], qw["final_norm"], shape.norm_eps)
    return gemm(hid, qw["head"], arith)


def gaps(qw, shape: Shape, arith: Arith, prompt, served, *, seq_len: int,
         n_read: int, control: Optional[dict] = None):
    """Per served token: how far its reference logit lies below the
    reference's best, given the prompt and the tokens served before it.

    With ``control`` (quantized weights of a lower-precision arithmetic,
    ``{"qw": ..., "arith": ...}``) also the gap of the token the control
    puts first at each of those positions."""
    prompt = np.asarray(prompt, np.int64)
    served = np.asarray(served, np.int64)
    n = len(served)
    seq = np.concatenate([prompt, served[:-1]])
    if len(seq) > seq_len or n > n_read:
        raise ValueError(f"sequence {len(seq)} / {n} tokens exceed the "
                         f"reference's {seq_len} / {n_read}")
    tokens = np.zeros(seq_len, np.int32)
    tokens[:len(seq)] = seq
    read = np.full(n_read, len(prompt) - 1, np.int32)
    read[:n] = len(prompt) - 1 + np.arange(n)
    logits = logits_at(qw, jnp.asarray(tokens), jnp.asarray(read),
                       shape=shape, arith=arith)
    lg = np.asarray(logits)[:n]
    best = lg.max(axis=-1)
    out = {"served": best - lg[np.arange(n), served]}
    if control is not None:
        cl = logits_at(control["qw"], jnp.asarray(tokens), jnp.asarray(read),
                       shape=shape, arith=control["arith"])
        first = np.asarray(jnp.argmax(cl, axis=-1))[:n]
        out["control"] = best - lg[np.arange(n), first]
    return out
