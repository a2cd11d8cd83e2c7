"""The DeepSeek-V2 family: multi-head latent attention (MLA, no q
compression) with yarn-scaled rope, a dense SwiGLU first layer, then
sparse layers of routed experts beside shared experts, and an untied head
(DeepSeek-V2-Lite). The family interface is ``families/dense.py``'s.

The chip's share of an expert-parallel deployment: the file's
``n_routed_experts`` experts (0 up to it) are held here, out of the
router's ``published["n_routed_experts"]``. The router scores all of them
and picks the top ``num_experts_per_tok`` by softmax score; only choices of
a held expert add to the layer's output, each scaled by its score (no
renormalisation where ``norm_topk_prob`` is false). What the absent
experts would add is left out, as the system leaves it out.

The expert layer here is a sum over the held experts of each one applied
to every token and weighted by the token's score for it (zero where it
did not choose it): no sort, no capacity, no dropped token. The loss adds
DeepSeek-V2's per-sequence balance loss over all the router's experts,
``aux_loss_alpha * mean_b sum_j (count_bj / (S k / E)) * mean_s P_bsj``
per sparse layer.

Departures from the published model, beside those ``reference.py`` lists:
rope in half-split (rotate-half) form, which the published interleaved
q_pe/k_pe layout equals up to a fixed permutation of the rope columns of
``wq`` and ``wkv_a``; routing drop-free, as the published modelling code
runs (the paper's training-time device-level dropping is not there).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import F32, _rms, padded_vocab, weighted_nll

GAINS = ("ln1", "ln2", "final_norm", "kv_norm")
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "moe_intermediate_size": 32, "n_routed_experts": 4,
        "num_experts_per_tok": 3, "n_shared_experts": 2, "vocab_size": 512,
        "published": {"n_routed_experts": 16}}


def router_experts(cfg: dict) -> int:
    """The router's width: every routed expert of the deployment."""
    return cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"])


def _yarn(cfg: dict) -> dict:
    return cfg["rope_scaling"] if (cfg.get("rope_scaling") or {}).get("type") == "yarn" else None


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_bounds(cfg: dict) -> tuple:
    """(low, high): the rope dims between which yarn's ramp runs."""
    y, dim, theta = _yarn(cfg), cfg["qk_rope_head_dim"], cfg["rope_theta"]

    def at(turns):
        return (dim * math.log(y["original_max_position_embeddings"] / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    return max(math.floor(at(y["beta_fast"])), 0), min(math.ceil(at(y["beta_slow"])), dim - 1)


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    y = _yarn(cfg)
    if y and y.get("mscale_all_dim"):
        scale *= _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def model_config(cfg: dict, name: str):
    """The system's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig

    y = _yarn(cfg) or {}
    return ModelConfig(
        name=name, arch_type="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], use_mla=True, kv_lora_rank=cfg["kv_lora_rank"],
        q_lora_rank=cfg.get("q_lora_rank") or 0, qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        n_experts=router_experts(cfg), experts_held=(0, cfg["n_routed_experts"]),
        n_shared_experts=cfg["n_shared_experts"], top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"], first_dense_layers=cfg["first_k_dense_replace"],
        capacity_factor=0.0, norm_topk=bool(cfg["norm_topk_prob"]),
        aux_loss="seq" if cfg.get("seq_aux") else "switch",
        aux_weight=float(cfg["aux_loss_alpha"]), rope_theta=float(cfg["rope_theta"]),
        yarn_factor=float(y.get("factor", 0.0)),
        yarn_original_max_pos=int(y.get("original_max_position_embeddings", 4096)),
        yarn_beta_fast=float(y.get("beta_fast", 32)), yarn_beta_slow=float(y.get("beta_slow", 1)),
        yarn_mscale=float(y.get("mscale", 1.0)),
        yarn_mscale_all_dim=float(y.get("mscale_all_dim", 0.0)),
        norm_eps=float(cfg["rms_norm_eps"]), tie_embeddings=bool(cfg["tie_word_embeddings"]),
        remat=True)


# ------------------------------------------------------------------ weights


def _attn_shapes(cfg: dict, n: int) -> dict:
    d, H, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return {"wq": (n, d, H * (dn + dr)), "wkv_a": (n, d, r + dr), "kv_norm": (n, r),
            "wkv_b": (n, r, H * (dn + dv)), "wo": (n, H * dv, d)}


def weight_shapes(cfg: dict) -> dict:
    """Shapes of the trained parameter pytree, layer-stacked on axis 0."""
    d, V = cfg["hidden_size"], padded_vocab(cfg["vocab_size"])
    nd = cfg["first_k_dense_replace"]
    nm = cfg["num_hidden_layers"] - nd
    ff, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    G, fs = cfg["n_routed_experts"], cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    return {"emb": {"tok": (V, d)}, "final_norm": (d,), "head": (d, V),
            "dense_layers": {"ln1": (nd, d), "ln2": (nd, d), "attn": _attn_shapes(cfg, nd),
                             "ffn": {"gate": (nd, d, ff), "up": (nd, d, ff),
                                     "down": (nd, ff, d)}},
            "moe_layers": {"ln1": (nm, d), "ln2": (nm, d), "attn": _attn_shapes(cfg, nm),
                           "ffn": {"router": (nm, d, router_experts(cfg)),
                                   "gate": (nm, G, d, f), "up": (nm, G, d, f),
                                   "down": (nm, G, f, d),
                                   "shared": {"gate": (nm, d, fs), "up": (nm, d, fs),
                                              "down": (nm, fs, d)}}}}


def init_std(path: str, cfg: dict) -> float:
    d = cfg["hidden_size"]
    if path.endswith("['tok']"):
        return 0.02
    if path.endswith("['wkv_b']"):
        return cfg["kv_lora_rank"] ** -0.5
    if path.endswith("['wo']"):
        return (cfg["num_attention_heads"] * cfg["v_head_dim"]) ** -0.5
    if path.endswith("['shared']['down']"):
        return (cfg["moe_intermediate_size"] * cfg["n_shared_experts"]) ** -0.5
    if path.endswith("['down']"):
        return (cfg["moe_intermediate_size"] if "moe_layers" in path
                else cfg["intermediate_size"]) ** -0.5
    return d ** -0.5


# ------------------------------------------------------------------ model


def _rope(x, cfg):
    """Rotate-half rope at positions 0..S-1, yarn-scaled where the file
    says; x is (B, S, heads, dr)."""
    S, dr, theta = x.shape[1], x.shape[-1], cfg["rope_theta"]
    inv = 1.0 / (theta ** (np.arange(0, dr, 2, dtype=np.float64) / dr))
    m = 1.0
    y = _yarn(cfg)
    if y:
        low, high = yarn_bounds(cfg)
        ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3), 0, 1)
        inv = inv / y["factor"] * ramp + inv * (1 - ramp)
        m = _mscale(y["factor"], y["mscale"]) / _mscale(y["factor"], y.get("mscale_all_dim", 0))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(m * np.cos(ang), F32)[None, :, None, :]
    sin = jnp.asarray(m * np.sin(ang), F32)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).astype(x.dtype)


def _mla(cfg, h, a):
    B, S, _ = h.shape
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q = (h @ a["wq"]).reshape(B, S, H, dn + dr)
    qn, qr = q[..., :dn], _rope(q[..., dn:], cfg)
    kv_a = h @ a["wkv_a"]
    c = _rms(kv_a[..., :r], a["kv_norm"], cfg["rms_norm_eps"])
    kr = _rope(kv_a[..., None, r:], cfg)[:, :, 0]           # one rope key for all heads
    kv = (c @ a["wkv_b"]).reshape(B, S, H, dn + dv)
    kn, v = kv[..., :dn], kv[..., dn:]
    s = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn)
         + jnp.einsum("bqhd,bkd->bhqk", qr, kr)).astype(F32) * softmax_scale(cfg)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1).astype(h.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H * dv)
    return o @ a["wo"]


def _swiglu(f, h):
    return (jax.nn.silu(h @ f["gate"]) * (h @ f["up"])) @ f["down"]


def routed(cfg, h, f, first=0):
    """The held experts' part of a sparse layer and its balance loss:
    experts ``first`` up to ``first`` plus the number held. h is (B, S, d)."""
    k, E = cfg["num_experts_per_tok"], router_experts(cfg)
    probs = jax.nn.softmax((h.astype(F32) @ f["router"].astype(F32)), axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        topv = topv / jnp.sum(topv, -1, keepdims=True)
    y = jnp.zeros_like(h)
    for g in range(f["gate"].shape[0]):
        score = jnp.sum(jnp.where(topi == first + g, topv, 0.0), -1).astype(h.dtype)
        hg = jax.nn.silu(h @ f["gate"][g]) * (h @ f["up"][g])
        y = y + score[..., None] * (hg @ f["down"][g])
    S = h.shape[1]
    count = jnp.sum(jax.nn.one_hot(topi, E, dtype=F32), axis=(1, 2))      # (B, E)
    aux = jnp.mean(jnp.sum(count / (S * k / E) * jnp.mean(probs, axis=1), -1))
    return y, aux


def _layer(cfg, x, lp, sparse):
    eps = cfg["rms_norm_eps"]
    x = x + _mla(cfg, _rms(x, lp["ln1"], eps), lp["attn"])
    h = _rms(x, lp["ln2"], eps)
    if not sparse:
        return x + _swiglu(lp["ffn"], h), 0.0
    y, aux = routed(cfg, h, lp["ffn"])
    return x + y + _swiglu(lp["ffn"]["shared"], h), aux


def loss_fn(params, cfg, tokens, row_w=None):
    """Weighted next-position cross entropy of token rows (B, S), plus the
    balance loss of the sparse layers."""
    x = params["emb"]["tok"][tokens]
    # each layer recomputed in the backward pass, so that one layer's
    # activations at a time are live at the cells' 2,048-token rows
    dense = jax.checkpoint(lambda c, lp: (_layer(cfg, c, lp, False)[0], None))
    x, _ = jax.lax.scan(dense, x, params["dense_layers"])
    x, aux = jax.lax.scan(jax.checkpoint(lambda c, lp: _layer(cfg, c, lp, True)), x,
                          params["moe_layers"])
    x = _rms(x, params["final_norm"], cfg["rms_norm_eps"])
    nll = weighted_nll((x @ params["head"]).astype(F32), tokens, row_w)
    return nll + cfg["aux_loss_alpha"] * jnp.sum(aux)


# ------------------------------------------------------------------ counts
#
# The dense family's convention (PaLM: 6 FLOPs per matmul parameter per
# token, nothing for recomputation), with the routed experts at their
# expectation: each token's k choices land on a held expert with the
# share held / router width, so a token uses k * held / E experts' worth
# of routed weights. MLA's attention counts 12 * L * H * (dqk + dv) / 2 *
# seq, the dense family's 12 * L * H * hd * seq with hd the mean of the
# score and value head dims.


def held_share(cfg: dict) -> float:
    return cfg["n_routed_experts"] / router_experts(cfg)


def _attn_matmul(cfg: dict) -> int:
    return int(sum(math.prod(s[1:]) for k, s in _attn_shapes(cfg, 1).items() if k != "kv_norm"))


def matmul_params(cfg: dict) -> float:
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    nd = cfg["first_k_dense_replace"]
    nm = cfg["num_hidden_layers"] - nd
    dense = _attn_matmul(cfg) + 3 * d * cfg["intermediate_size"]
    sparse = (_attn_matmul(cfg) + d * router_experts(cfg)
              + 3 * d * f * (cfg["n_shared_experts"]
                             + cfg["num_experts_per_tok"] * held_share(cfg)))
    return nd * dense + nm * sparse + d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    hd = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]) / 2
    attn = 12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * hd * seq
    return 6.0 * matmul_params(cfg) + attn


def trained_params(cfg: dict, padded_vocab: int) -> int:
    """Every trained float of the model as the system holds it (the
    vocabulary padded as it is stored): the length of one flat update."""
    shapes = dict(weight_shapes(cfg), emb={"tok": (padded_vocab, cfg["hidden_size"])},
                  head=(cfg["hidden_size"], padded_vocab))
    return int(sum(math.prod(s) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple))))


def gmm_counts(cfg: dict, rows: int, groups: int, a: int, b: int) -> tuple:
    """(FLOPs, bytes) of one float32 grouped matmul over ``rows`` sorted
    (token, choice) rows, ``rows * held_share`` of them routed to a held
    expert at expectation, between widths ``a`` and ``b`` of ``groups``
    experts: the routed rows' operand and result, and the experts'
    weights (or their gradient) once."""
    routed_rows = rows * held_share(cfg)
    return 2.0 * routed_rows * a * b, 4.0 * (routed_rows * (a + b) + groups * a * b)
