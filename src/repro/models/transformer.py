"""Decoder-only LM assembly for dense / MoE / MLA architectures.

Layer stacks are lax.scan'd over stacked params (HLO O(1) in depth).
Heterogeneous stacks (deepseek's dense first layer) are two scans.
Optionally remats each layer and applies Megatron-style sequence-sharding
constraints at layer boundaries (see repro.sharding.partition.constrain).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.layers import (cross_entropy, dtype_of, embed,
                                 init_embedding, init_swiglu, normal,
                                 rms_norm, stacked_init, swiglu)
from repro.models.moe import init_moe, moe_ffn
from repro.sharding.partition import constrain


# ----------------------------------------------------------------- init

def _init_block(key, cfg, kind):
    k1, k2 = jax.random.split(key)
    dt = dtype_of(cfg)
    p = {"ln1": jnp.ones((cfg.d_model,), dt),
         "ln2": jnp.ones((cfg.d_model,), dt)}
    if cfg.use_mla:
        p["attn"] = attn.init_mla(k1, cfg)
    else:
        p["attn"] = attn.init_attention(k1, cfg)
    if kind == "moe":
        p["ffn"] = init_moe(k2, cfg)
    else:
        p["ffn"] = init_swiglu(k2, cfg.d_model, cfg.d_ff, dt)
    return p


def init_lm(key, cfg):
    dt = dtype_of(cfg)
    k_emb, k_dense, k_moe, k_head = jax.random.split(key, 4)
    n_dense = cfg.first_dense_layers if cfg.is_moe else cfg.n_layers
    n_moe = cfg.n_layers - n_dense if cfg.is_moe else 0
    params = {
        "emb": init_embedding(k_emb, cfg.padded_vocab, cfg.d_model, dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if n_dense:
        params["dense_layers"] = stacked_init(
            lambda k: _init_block(k, cfg, "dense"), k_dense, n_dense)
    if n_moe:
        params["moe_layers"] = stacked_init(
            lambda k: _init_block(k, cfg, "moe"), k_moe, n_moe)
    if not cfg.tie_embeddings:
        params["head"] = normal(k_head, (cfg.d_model, cfg.padded_vocab),
                                cfg.d_model ** -0.5, dt)
    return params


# ----------------------------------------------------------------- blocks

def _block_apply(p, cfg, x, positions, kind, mode, cache=None, pos=None,
                 moe_groups=1):
    """One transformer block. Returns (x, new_cache, aux)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    new_cache = None
    if cfg.use_mla:
        if mode == "train":
            a = attn.mla_train(p["attn"], cfg, h, positions)
        elif mode == "prefill":
            a, new_cache = attn.mla_prefill(p["attn"], cfg, h, positions)
        else:
            a, new_cache = attn.mla_decode(p["attn"], cfg, h, pos, cache,
                                           absorb=cfg.mla_absorb)
    else:
        if mode == "train":
            a = attn.attn_train(p["attn"], cfg, h, positions)
        elif mode == "prefill":
            a, new_cache = attn.attn_prefill(p["attn"], cfg, h, positions)
        else:
            a, new_cache = attn.attn_decode(p["attn"], cfg, h, pos, cache)
    x = x + a
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    aux = 0.0
    if kind == "moe":
        f, aux = moe_ffn(p["ffn"], cfg, h, groups=moe_groups)
    else:
        f = swiglu(p["ffn"], h)
    x = constrain(x + f, "activation")
    return x, new_cache, aux


def _scan_stack(layers, cfg, x, positions, kind, mode, caches=None,
                pos=None, moe_groups=1):
    """Scan a homogeneous stack. caches stacked on axis 0 (decode)."""

    def body(carry, xs):
        xc, aux_sum = carry
        if mode == "decode":
            p_l, c_l = xs
        else:
            p_l, c_l = xs, None
        xc, new_c, aux = _block_apply(p_l, cfg, xc, positions, kind, mode,
                                      cache=c_l, pos=pos,
                                      moe_groups=moe_groups)
        return (xc, aux_sum + aux), new_c

    if cfg.remat:
        body = jax.checkpoint(body)
    xs = (layers, caches) if mode == "decode" else layers
    (x, aux), new_caches = jax.lax.scan(body, (x, 0.0), xs)
    return x, aux, new_caches


def lm_backbone(params, cfg, x, positions, mode, caches=None, pos=None,
                moe_groups=1):
    """Runs all layer stacks. caches: {'dense':..., 'moe':...} or None."""
    aux_total = 0.0
    new_caches = {}
    if "dense_layers" in params:
        c = caches.get("dense") if caches else None
        x, aux, nc = _scan_stack(params["dense_layers"], cfg, x, positions,
                                 "dense", mode, c, pos, moe_groups)
        aux_total += aux
        new_caches["dense"] = nc
    if "moe_layers" in params:
        c = caches.get("moe") if caches else None
        x, aux, nc = _scan_stack(params["moe_layers"], cfg, x, positions,
                                 "moe", mode, c, pos, moe_groups)
        aux_total += aux
        new_caches["moe"] = nc
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_total, new_caches


def lm_logits(params, cfg, x):
    head = params.get("head", None)
    w = head if head is not None else params["emb"]["tok"].T
    return constrain(x @ w, "logits")


# ----------------------------------------------------------------- entry

def embed_inputs(params, cfg, batch):
    """tokens (+ optional img embeds for VLM) -> (B, S, d) activations."""
    x = embed(params["emb"], batch["tokens"])
    if cfg.n_img_tokens and "img_embeds" in batch:
        x = jnp.concatenate(
            [batch["img_embeds"].astype(x.dtype), x], axis=1)
    return x


def lm_loss(params, cfg, batch, moe_groups=1):
    x = embed_inputs(params, cfg, batch)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x, aux, _ = lm_backbone(params, cfg, x, positions, "train",
                            moe_groups=moe_groups)
    logits = lm_logits(params, cfg, x)
    labels = batch["labels"]
    if labels.shape[1] < S:                    # VLM: no loss on img tokens
        pad = -jnp.ones((B, S - labels.shape[1]), labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    mask = (labels >= 0).astype(jnp.float32)
    if "client_weights" in batch:              # MMFL p_k aggregation weights
        mask = mask * batch["client_weights"][:, None]
    loss = cross_entropy(logits, jnp.maximum(labels, 0), mask)
    if cfg.is_moe:
        loss = loss + cfg.aux_weight * aux
    return loss, {"aux": aux}


def lm_prefill(params, cfg, batch, moe_groups=1):
    x = embed_inputs(params, cfg, batch)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x, _, caches = lm_backbone(params, cfg, x, positions, "prefill",
                               moe_groups=moe_groups)
    logits = lm_logits(params, cfg, x[:, -1:, :])
    return logits, caches


def init_lm_cache(params, cfg, batch_size, length, dtype, per_row=False):
    caches = {}
    if "dense_layers" in params:
        n = jax.tree.leaves(params["dense_layers"])[0].shape[0]
        caches["dense"] = _stack_caches(cfg, batch_size, length, dtype, n,
                                        per_row)
    if "moe_layers" in params:
        n = jax.tree.leaves(params["moe_layers"])[0].shape[0]
        caches["moe"] = _stack_caches(cfg, batch_size, length, dtype, n,
                                      per_row)
    return caches


def _stack_caches(cfg, batch_size, length, dtype, n, per_row=False):
    if cfg.use_mla:
        assert not per_row, "per-row decode: GQA caches only (see queue.py)"
        one = attn.init_mla_cache(cfg, batch_size, length, dtype)
    else:
        one = attn.init_cache(cfg, batch_size, length, dtype,
                              per_row=per_row)
    return jax.tree.map(lambda t: jnp.broadcast_to(t, (n,) + t.shape), one)


def lm_decode(params, cfg, token, pos, caches, moe_groups=1):
    """token: (B,1) int32; pos: scalar int32; caches from prefill/init."""
    x = embed(params["emb"], token)
    x, _, new_caches = lm_backbone(params, cfg, x, None, "decode",
                                   caches=caches, pos=pos,
                                   moe_groups=moe_groups)
    return lm_logits(params, cfg, x), new_caches
