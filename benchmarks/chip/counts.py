"""Operations and bytes of the work the cells run, from shapes alone.

Model FLOPs follow the PaLM convention: forward plus backward is 6 FLOPs
per matmul parameter per token, plus 12 * layers * heads * head_dim *
seq for attention (the masked half of causal attention included), with
nothing counted for recomputation. The embedding lookup is not a matmul;
the tied output head is. The published vocabulary is counted, not the
padded one.
"""
from __future__ import annotations

F32_BYTES = 4


def _hd(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def matmul_params(cfg: dict) -> int:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], _hd(cfg)
    per_layer = d * H * hd * 2 + d * KV * hd * 2 + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    attn = 12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * _hd(cfg) * seq
    return 6.0 * matmul_params(cfg) + attn


def trained_params(cfg: dict, padded_vocab: int) -> int:
    """Every trained float of the model as the system holds it (the
    vocabulary padded as it is stored): the length of one flat update."""
    d, ff, L = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], _hd(cfg)
    per_layer = 2 * d + d * H * hd * 2 + d * KV * hd * 2 + 3 * d * ff
    if cfg.get("qk_norm"):
        per_layer += 2 * hd
    return padded_vocab * d + d + L * per_layer


def fedavg_fold_bytes(k: int, n: int) -> int:
    """Weighted sum of a (K, N) f32 cohort: K*N read, N written."""
    return (k * n + n) * F32_BYTES


def fedadam_fold_bytes(k: int, n: int) -> int:
    """Fused FedAdam flush: K*N deltas and the N-sized m, v read; the
    N-sized update, m and v written."""
    return (k * n + 2 * n + 3 * n) * F32_BYTES
