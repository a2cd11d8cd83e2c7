"""The dense decoder family: a GQA decoder LM with RMSNorm, RoPE, an
optional per-head qk-norm, a SwiGLU feed-forward and a tied head
(smollm-135m, qwen3-0.6b). A configuration file with no ``family`` key
is of this family.

What a family module defines, found by ``reference.family`` from the
configuration file's ``family`` key:

- ``model_config(cfg, name)``: the system's ``ModelConfig``; the only
  function here that imports the system, and only when it is called;
- ``weight_shapes(cfg)``, ``init_std(path, cfg)`` and ``GAINS``: the
  trained pytree's shapes in the system's layout, the fan-in scale of
  each matrix, and the leaves made as ones (the norm gains, which
  ``check.compare`` also reads);
- ``loss_fn(params, cfg, tokens, row_w)``: the plain forward pass and the
  system's loss, in straightforward ``jax.numpy``;
- ``matmul_params``, ``train_flops_per_token`` and ``trained_params``:
  the counts that ``counts.py`` hands to the metric readers;
- ``TINY``: the widths, depth and vocabulary the CPU tests cut to.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import F32, _rms, _rope, padded_vocab, weighted_nll

GAINS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")
# a configuration that gives head_dim takes 16 in the tests, which is
# hidden_size / num_attention_heads at these widths
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512}


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def model_config(cfg: dict, name: str):
    """The system's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=name, arch_type="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=cfg.get("head_dim") or 0,
        qk_norm=bool(cfg.get("qk_norm")), rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]))


# ------------------------------------------------------------------ weights


def weight_shapes(cfg: dict) -> dict:
    """Shapes of the trained parameter pytree, layer-stacked on axis 0."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    ff, V = cfg["intermediate_size"], padded_vocab(cfg["vocab_size"])
    attn = {"wq": (L, d, H * hd), "wk": (L, d, KV * hd), "wv": (L, d, KV * hd),
            "wo": (L, H * hd, d)}
    if cfg.get("qk_norm"):
        attn["q_norm"] = (L, hd)
        attn["k_norm"] = (L, hd)
    return {"emb": {"tok": (V, d)}, "final_norm": (d,),
            "dense_layers": {"ln1": (L, d), "ln2": (L, d), "attn": attn,
                             "ffn": {"gate": (L, d, ff), "up": (L, d, ff),
                                     "down": (L, ff, d)}}}


def init_std(path: str, cfg: dict) -> float:
    d = cfg["hidden_size"]
    if path.endswith("tok"):
        return 0.02
    if path.endswith("wo"):
        return (cfg["num_attention_heads"] * head_dim(cfg)) ** -0.5
    if path.endswith("down"):
        return cfg["intermediate_size"] ** -0.5
    return d ** -0.5


# ------------------------------------------------------------------ model


def _layer(cfg, x, lp):
    B, S, d = x.shape
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    eps = cfg["rms_norm_eps"]
    a = lp["attn"]
    h = _rms(x, lp["ln1"], eps)
    q = (h @ a["wq"]).reshape(B, S, H, hd)
    k = (h @ a["wk"]).reshape(B, S, KV, hd)
    v = (h @ a["wv"]).reshape(B, S, KV, hd)
    if cfg.get("qk_norm"):
        q = _rms(q, a["q_norm"], eps)
        k = _rms(k, a["k_norm"], eps)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    # query head h reads key/value head h // (H / KV)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(F32) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H * hd)
    x = x + o @ a["wo"]
    f = lp["ffn"]
    h = _rms(x, lp["ln2"], eps)
    return x + (jax.nn.silu(h @ f["gate"]) * (h @ f["up"])) @ f["down"]


def loss_fn(params, cfg, tokens, row_w=None):
    """Weighted next-position cross entropy of token rows (B, S)."""
    x = params["emb"]["tok"][tokens]
    x, _ = jax.lax.scan(lambda c, lp: (_layer(cfg, c, lp), None), x,
                        params["dense_layers"])
    x = _rms(x, params["final_norm"], cfg["rms_norm_eps"])
    return weighted_nll((x @ params["emb"]["tok"].T).astype(F32), tokens, row_w)


# ------------------------------------------------------------------ counts
#
# PaLM convention: forward plus backward is 6 FLOPs per matmul parameter
# per token, plus 12 * layers * heads * head_dim * seq for attention (the
# masked half of causal attention included), with nothing counted for
# recomputation. The embedding lookup is not a matmul; the tied output
# head is. The published vocabulary is counted, not the padded one.


def matmul_params(cfg: dict) -> int:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    per_layer = d * H * hd * 2 + d * KV * hd * 2 + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    attn = 12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * head_dim(cfg) * seq
    return 6.0 * matmul_params(cfg) + attn


def trained_params(cfg: dict, padded_vocab: int) -> int:
    """Every trained float of the model as the system holds it (the
    vocabulary padded as it is stored): the length of one flat update."""
    d, ff, L = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    per_layer = 2 * d + d * H * hd * 2 + d * KV * hd * 2 + 3 * d * ff
    if cfg.get("qk_norm"):
        per_layer += 2 * hd
    return padded_vocab * d + d + L * per_layer
