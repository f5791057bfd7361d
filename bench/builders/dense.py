"""Builder of dense GQA transformer configurations served by ``ServeEngine``
(a configuration file names it with ``"builder": "dense"``).

A configuration file (``configs/<name>.json``) states the published sizes
under Hugging Face's key names, what was cut (``reduced``), what was assumed,
the GEMM policy and the engine options. ``build`` makes the weights on the
device from the seed in one jitted call, binds them to the policy, drops
the raw weights and returns what the harness serves. ``reference_shape``
and ``make_weights`` give the plain reference the same sizes and the same
seeded weights.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from bench.reference import dense_lm
from bench.harness import seeds

BF16 = jnp.bfloat16
NORM_STD = 0.1       # spread of the RMSNorm gains and the QKV biases


@dataclasses.dataclass
class Built:
    cfg: Any                  # repro ModelConfig
    params: Any               # bound parameters on the device
    policy: Any               # repro GemmPolicy
    engine_kwargs: Dict[str, Any]


def reference_shape(conf: Dict[str, Any]) -> dense_lm.Shape:
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return dense_lm.Shape(
        n_layers=conf["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim", d // h),
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tied=bool(conf["tie_word_embeddings"]),
        qkv_bias=bool(conf.get("attention_bias", False)))


def model_config(conf: Dict[str, Any]):
    """The program's ModelConfig for these sizes (its registry entry, with
    every size taken from the configuration file)."""
    from repro.configs import ARCHS
    s = reference_shape(conf)
    return dataclasses.replace(
        ARCHS[conf["arch"]], n_layers=s.n_layers, d_model=s.d_model,
        n_heads=s.n_heads, n_kv_heads=s.n_kv_heads, head_dim=s.head_dim,
        d_ff=s.d_ff, vocab_size=s.vocab, rope_theta=s.rope_theta,
        norm_eps=s.norm_eps, tie_embeddings=s.tied, qkv_bias=s.qkv_bias,
        dtype="bfloat16")


def _make(shape: dense_lm.Shape, key):
    d, h, kh, hd = shape.d_model, shape.n_heads, shape.n_kv_heads, shape.head_dim
    ell, ff, v = shape.n_layers, shape.d_ff, shape.vocab
    leaves = {
        "embed": ((v, d), d ** -0.5),
        "final_norm": ((d,), NORM_STD),
        "layers/ln1": ((ell, d), NORM_STD),
        "layers/ln2": ((ell, d), NORM_STD),
        "layers/attn/wq": ((ell, d, h * hd), d ** -0.5),
        "layers/attn/wk": ((ell, d, kh * hd), d ** -0.5),
        "layers/attn/wv": ((ell, d, kh * hd), d ** -0.5),
        "layers/attn/wo": ((ell, h * hd, d), (h * hd) ** -0.5),
        "layers/mlp/w1": ((ell, d, ff), d ** -0.5),
        "layers/mlp/w3": ((ell, d, ff), d ** -0.5),
        "layers/mlp/w2": ((ell, ff, d), ff ** -0.5),
    }
    if shape.qkv_bias:
        leaves["layers/attn/bq"] = ((ell, h * hd), NORM_STD)
        leaves["layers/attn/bk"] = ((ell, kh * hd), NORM_STD)
        leaves["layers/attn/bv"] = ((ell, kh * hd), NORM_STD)
    if not shape.tied:
        leaves["lm_head"] = ((d, v), d ** -0.5)
    out: Dict[str, Any] = {}
    for i, (path, (shp, std)) in enumerate(sorted(leaves.items())):
        arr = (jax.random.normal(jax.random.fold_in(key, i), shp, jnp.float32)
               * std).astype(BF16)
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = arr
    return out


def make_weights(conf: Dict[str, Any], seed: int):
    """The program's parameter pytree, made on the device from the seed in
    one jitted call: bf16 weights with fan-in scaled normal entries,
    RMSNorm gains ``1 + w`` and QKV biases with w ~ N(0, 0.1^2)."""
    shape = reference_shape(conf)
    key = jnp.asarray(seeds.key_words(seed, seeds.WEIGHTS))
    return jax.jit(_make, static_argnums=0)(shape, key)


def reference_weights(conf: Dict[str, Any], weights):
    """The program's pytree re-keyed for ``reference.dense_lm``."""
    lay = weights["layers"]
    flat = {"embed": weights["embed"], "final_norm": weights["final_norm"],
            "layers": {"ln1": lay["ln1"], "ln2": lay["ln2"],
                       **lay["attn"], **lay["mlp"]}}
    if "lm_head" in weights:
        flat["lm_head"] = weights["lm_head"]
    return flat


def policy(conf: Dict[str, Any]):
    from repro.core.gemm import GemmPolicy
    g = conf["gemm"]
    return GemmPolicy(backend=g["backend"], k=g.get("k", 4),
                      n_bits=g.get("n_bits", 8))


def build(conf: Dict[str, Any], seed: int) -> Built:
    """Weights from the seed, bound to the configuration's GEMM policy; the
    raw weights are dropped before the caller allocates the KV pool."""
    from repro.models import get_model
    cfg = model_config(conf)
    pol = policy(conf)
    raw = make_weights(conf, seed)
    params = get_model(cfg).bind_params(raw, pol, cached=False)
    jax.block_until_ready(params)
    del raw
    return Built(cfg, params, pol, dict(conf["engine"]))


def reference(conf: Dict[str, Any], seed: int, *, n_bits: int = 8):
    """(quantized reference weights, shape, arithmetic) for the configuration
    at operand width ``n_bits``."""
    shape = reference_shape(conf)
    arith = dense_lm.Arith(n_bits=n_bits, k=(conf["gemm"].get("k", 0)
                                             if conf["gemm"]["backend"]
                                             .startswith("approx") else 0))
    w = reference_weights(conf, make_weights(conf, seed))
    qw = jax.jit(dense_lm.prepare, static_argnums=(1, 2))(w, shape, arith)
    return qw, shape, arith
