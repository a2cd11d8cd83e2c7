"""Attention: GQA (qk-norm, qkv-bias, RoPE, sliding-window), MLA, cross-attn.

Three entry modes:
  * ``attn_train``   — full-sequence causal (training / teacher forcing)
  * ``attn_prefill`` — full-sequence causal, also returns the filled KV cache
  * ``attn_decode``  — ONE new token against a fixed-size cache

The cache is a dict ``{"k","v","positions"}`` of length W. W == seq_len for
ordinary decode; W == cfg.sliding_window for long-context decode, in which
case slots roll (slot = pos % W) and the window falls out naturally by
overwrite. Keys are stored RoPE'd at their absolute positions.

Memory: training/prefill attention is computed in query chunks via a
checkpointed lax.scan so the S x S score matrix is never materialised
(O(chunk * S) live) — mandatory for the 32k prefill shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import (apply_rope, dtype_of, normal, rms_norm,
                                 yarn_mscale, yarn_of)

Q_CHUNK = 512


def _maybe_lora(w, lora, name):
    if lora is None or f"a_{name}" not in lora:
        return w
    return w + lora[f"a_{name}"] @ lora[f"b_{name}"]


def init_attention(key, cfg, cross=False):
    """GQA projection params. cross=True: kv projected from encoder states."""
    dt = dtype_of(cfg)
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    std = d ** -0.5
    p = {
        "wq": normal(ks[0], (d, H * hd), std, dt),
        "wk": normal(ks[1], (d, KV * hd), std, dt),
        "wv": normal(ks[2], (d, KV * hd), std, dt),
        "wo": normal(ks[3], (H * hd, d), (H * hd) ** -0.5, dt),
    }
    if cfg.qkv_bias or cross:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((KV * hd,), dt)
        p["bv"] = jnp.zeros((KV * hd,), dt)
        p["bo"] = jnp.zeros((d,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def init_attention_lora(key, cfg, n_slots, rank):
    """Per-invocation LoRA adapters for a shared attention block (zamba2)."""
    dt = dtype_of(cfg)
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 3)
    std = d ** -0.5

    def one(k, out):
        ka, kb = jax.random.split(k)
        return (normal(ka, (n_slots, d, rank), std, dt),
                jnp.zeros((n_slots, rank, out), dt))

    aq, bq = one(ks[0], H * hd)
    ak, bk = one(ks[1], KV * hd)
    av, bv = one(ks[2], KV * hd)
    return {"a_q": aq, "b_q": bq, "a_k": ak, "b_k": bk, "a_v": av, "b_v": bv}


def _project_qkv(p, cfg, x, lora=None):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ _maybe_lora(p["wq"], lora, "q") + p.get("bq", 0.0)
    k = x @ _maybe_lora(p["wk"], lora, "k") + p.get("bk", 0.0)
    v = x @ _maybe_lora(p["wv"], lora, "v") + p.get("bv", 0.0)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _sdpa_chunked(q, k, v, q_pos, k_pos, scale, causal=True, window=0,
                  chunk=Q_CHUNK):
    """Chunked softmax attention. q:(B,Sq,H,hd) k/v:(B,Sk,KV,*).

    GQA via reshape; scores masked with absolute positions (k_pos < 0 =
    invalid slot). Scanned over query chunks, each chunk checkpointed, so
    live memory is O(chunk x Sk) instead of O(Sq x Sk).
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    G = H // KV
    qr = q.reshape(B, Sq, KV, G, hd)

    def block(q_blk, qp_blk):
        # q_blk: (B, c, KV, G, hd). bf16 operands + f32 accumulation — the
        # native MXU contract; avoids CPU-style f32 materialisation of the
        # big operands.
        s = jnp.einsum("bqkgh,bskh->bkgqs", q_blk, k,
                       preferred_element_type=jnp.float32) * scale
        mask = k_pos[None, :] >= 0                      # (1, Sk) valid slots
        if causal:
            mask = mask & (k_pos[None, :] <= qp_blk[:, None])
        if window:
            mask = mask & (k_pos[None, :] > qp_blk[:, None] - window)
        s = jnp.where(mask[None, None, None, :, :], s, -1e30)
        p_attn = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bkgqs,bskh->bqkgh", p_attn, v,
                       preferred_element_type=jnp.float32)
        return o.astype(q.dtype)

    block = jax.checkpoint(block)
    if Sq % chunk:
        # largest divisor of Sq not exceeding the requested chunk
        chunk = next(c for c in range(min(chunk, Sq), 0, -1) if Sq % c == 0)
    if Sq <= chunk:
        out = block(qr, q_pos)
    else:
        n = Sq // chunk
        qc = qr.reshape(B, n, chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
        pc = q_pos.reshape(n, chunk)

        def step(_, qp):
            return None, block(*qp)

        _, oc = jax.lax.scan(step, None, (qc, pc))
        out = oc.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, KV, G, dv)
    return out.reshape(B, Sq, H, dv)


def attn_train(p, cfg, x, positions, lora=None):
    q, k, v = _project_qkv(p, cfg, x, lora)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    B, S = x.shape[:2]
    if cfg.use_pallas and S % 128 == 0:
        # deployment path: Pallas flash attention (VMEM-resident scores)
        from repro.kernels import flash_attention
        o = flash_attention(q.transpose(0, 2, 1, 3),
                            k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3),
                            causal=True).transpose(0, 2, 1, 3)
    else:
        o = _sdpa_chunked(q, k, v, positions[0], positions[0],
                          cfg.hd ** -0.5, causal=True, window=0)
    return o.reshape(B, S, -1) @ p["wo"] + p.get("bo", 0.0)


def attn_prefill(p, cfg, x, positions, lora=None):
    q, k, v = _project_qkv(p, cfg, x, lora)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = _sdpa_chunked(q, k, v, positions[0], positions[0], cfg.hd ** -0.5)
    B, S = x.shape[:2]
    y = o.reshape(B, S, -1) @ p["wo"] + p.get("bo", 0.0)
    cache = {"k": k, "v": v, "positions": positions[0]}
    return y, cache


def init_cache(cfg, batch, length, dtype, kv_heads=None, head_dim=None,
               per_row=False):
    """per_row=True: each batch row decodes at its OWN position (continuous
    batching); positions become (B, W) instead of the shared (W,)."""
    KV = kv_heads or cfg.n_kv_heads
    hd = head_dim or cfg.hd
    pos_shape = (batch, length) if per_row else (length,)
    return {
        "k": jnp.zeros((batch, length, KV, hd), dtype),
        "v": jnp.zeros((batch, length, KV, hd), dtype),
        "positions": -jnp.ones(pos_shape, jnp.int32),
    }


def _sdpa_decode_perrow(q, k, v, q_pos, k_pos, scale, window=0):
    """Per-row decode attention: q (B,1,H,hd), k/v (B,W,KV,hd),
    q_pos (B,), k_pos (B,W)."""
    B, _, H, hd = q.shape
    W, KV = k.shape[1], k.shape[2]
    G = H // KV
    qr = q.reshape(B, 1, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qr, k,
                   preferred_element_type=jnp.float32) * scale
    mask = (k_pos >= 0) & (k_pos <= q_pos[:, None])
    if window:
        mask = mask & (k_pos > q_pos[:, None] - window)
    s = jnp.where(mask[:, None, None, None, :], s, -1e30)
    p_attn = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p_attn, v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype).reshape(B, 1, H, v.shape[-1])


def attn_decode(p, cfg, x, pos, cache, lora=None):
    """x: (B, 1, d); pos: scalar int32 absolute position — or (B,) vector
    when the cache was built with per_row=True (continuous batching).
    Cache length W; rolling slots (pos % W) give the sliding window."""
    B = x.shape[0]
    W = cache["k"].shape[1]
    per_row = cache["positions"].ndim == 2
    q, k, v = _project_qkv(p, cfg, x, lora)
    posv = (pos.astype(jnp.int32).reshape(B, 1) if per_row
            else jnp.full((B, 1), pos, jnp.int32))
    q = apply_rope(q, posv, cfg.rope_theta)
    k = apply_rope(k, posv, cfg.rope_theta)
    if per_row:
        rows = jnp.arange(B)
        slots = (posv[:, 0] % W).astype(jnp.int32)
        ck = cache["k"].at[rows, slots].set(k[:, 0])
        cv = cache["v"].at[rows, slots].set(v[:, 0])
        cpos = cache["positions"].at[rows, slots].set(posv[:, 0])
        o = _sdpa_decode_perrow(q, ck, cv, posv[:, 0], cpos,
                                cfg.hd ** -0.5,
                                window=cfg.sliding_window)
    else:
        slot = pos % W
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
        cpos = jax.lax.dynamic_update_slice(
            cache["positions"], pos[None].astype(jnp.int32), (slot,))
        o = _sdpa_chunked(q, ck, cv, posv[0], cpos, cfg.hd ** -0.5,
                          causal=True, window=cfg.sliding_window)
    y = o.reshape(B, 1, -1) @ p["wo"] + p.get("bo", 0.0)
    return y, {"k": ck, "v": cv, "positions": cpos}


# ---------------------------------------------------------------- cross-attn

def cross_kv(p, cfg, enc):
    """Precompute encoder K/V once per sequence (whisper serving)."""
    B, T, _ = enc.shape
    KV, hd = cfg.n_kv_heads, cfg.hd
    k = (enc @ p["wk"] + p.get("bk", 0.0)).reshape(B, T, KV, hd)
    v = (enc @ p["wv"] + p.get("bv", 0.0)).reshape(B, T, KV, hd)
    return k, v


def cross_attn(p, cfg, x, kv):
    """No mask, no rope: decoder attends to the (stub) encoder output."""
    B, S, _ = x.shape
    H, hd = cfg.n_heads, cfg.hd
    k, v = kv
    q = (x @ p["wq"] + p.get("bq", 0.0)).reshape(B, S, H, hd)
    T = k.shape[1]
    o = _sdpa_chunked(q, k, v, jnp.zeros((S,), jnp.int32),
                      jnp.zeros((T,), jnp.int32), hd ** -0.5, causal=False)
    return o.reshape(B, S, -1) @ p["wo"] + p.get("bo", 0.0)


# ======================================================================= MLA

def init_mla(key, cfg):
    """DeepSeek-V2 Multi-head Latent Attention (no q compression: V2-Lite)."""
    dt = dtype_of(cfg)
    d, H = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    ks = jax.random.split(key, 4)
    std = d ** -0.5
    return {
        "wq": normal(ks[0], (d, H * (dn + dr)), std, dt),
        "wkv_a": normal(ks[1], (d, r + dr), std, dt),
        "kv_norm": jnp.ones((r,), dt),
        "wkv_b": normal(ks[2], (r, H * (dn + dv)), r ** -0.5, dt),
        "wo": normal(ks[3], (H * dv, d), (H * dv) ** -0.5, dt),
    }


def _mla_q(p, cfg, x, positions):
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, dn + dr)
    qn, qr = q[..., :dn], q[..., dn:]
    qr = apply_rope(qr, positions, cfg.rope_theta, yarn_of(cfg))
    return qn, qr


def _mla_compress(p, cfg, x, positions):
    dr, r = cfg.qk_rope_head_dim, cfg.kv_lora_rank
    kv_a = x @ p["wkv_a"]                                  # (B,S,r+dr)
    c_kv = rms_norm(kv_a[..., :r], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv_a[..., r:], positions, cfg.rope_theta, yarn_of(cfg))
    return c_kv, k_rope


def _mla_expand(p, cfg, c_kv):
    B, S, _ = c_kv.shape
    H, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    kv = (c_kv @ p["wkv_b"]).reshape(B, S, H, dn + dv)
    return kv[..., :dn], kv[..., dn:]                      # k_nope, v


def mla_softmax_scale(cfg):
    """1/sqrt(q head dim), times yarn's mscale squared where yarn sets
    ``mscale_all_dim`` (DeepSeek-V2's softmax_scale)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if yarn_of(cfg) is not None and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _mla_sdpa(cfg, qn, qr, kn, kr, v, q_pos, k_pos, window=0):
    """MLA attention: scores = qn.kn + qr.kr (kr shared across heads)."""
    scale = mla_softmax_scale(cfg)
    q = jnp.concatenate([qn, qr], axis=-1)
    B, Sk = kn.shape[0], kn.shape[1]
    kr_b = jnp.broadcast_to(kr[:, :, None, :],
                            (B, Sk, cfg.n_heads, cfg.qk_rope_head_dim))
    k = jnp.concatenate([kn, kr_b], axis=-1)
    return _sdpa_chunked(q, k, v, q_pos, k_pos, scale, causal=True,
                         window=window)


def mla_train(p, cfg, x, positions):
    B, S, _ = x.shape
    with jax.named_scope("mla"):
        qn, qr = _mla_q(p, cfg, x, positions)
        c_kv, k_rope = _mla_compress(p, cfg, x, positions)
        kn, v = _mla_expand(p, cfg, c_kv)
        o = _mla_sdpa(cfg, qn, qr, kn, k_rope, v, positions[0], positions[0])
        return o.reshape(B, S, -1) @ p["wo"]


def mla_prefill(p, cfg, x, positions):
    B, S, _ = x.shape
    qn, qr = _mla_q(p, cfg, x, positions)
    c_kv, k_rope = _mla_compress(p, cfg, x, positions)
    kn, v = _mla_expand(p, cfg, c_kv)
    o = _mla_sdpa(cfg, qn, qr, kn, k_rope, v, positions[0], positions[0])
    cache = {"c_kv": c_kv, "k_rope": k_rope, "positions": positions[0]}
    return o.reshape(B, S, -1) @ p["wo"], cache


def init_mla_cache(cfg, batch, length, dtype):
    return {
        "c_kv": jnp.zeros((batch, length, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, length, cfg.qk_rope_head_dim), dtype),
        "positions": -jnp.ones((length,), jnp.int32),
    }


def mla_decode(p, cfg, x, pos, cache, absorb=False):
    """One token vs compressed cache.

    absorb=False (paper-faithful baseline): expand the whole cached latent
    through wkv_b each step. absorb=True (optimisation, DeepSeek-V2 §"absorb"):
    fold wkv_b into the query/output side so decode touches only the
    (r + dr)-wide latents — huge FLOP/byte saving at long context.
    """
    B = x.shape[0]
    W = cache["c_kv"].shape[1]
    posv = jnp.full((B, 1), pos, jnp.int32)
    qn, qr = _mla_q(p, cfg, x, posv)
    c_new, kr_new = _mla_compress(p, cfg, x, posv)
    slot = pos % W
    c_kv = jax.lax.dynamic_update_slice(cache["c_kv"], c_new, (0, slot, 0))
    k_rope = jax.lax.dynamic_update_slice(cache["k_rope"], kr_new,
                                          (0, slot, 0))
    cpos = jax.lax.dynamic_update_slice(
        cache["positions"], pos[None].astype(jnp.int32), (slot,))
    H, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    scale = mla_softmax_scale(cfg)
    if not absorb:
        kn, v = _mla_expand(p, cfg, c_kv)
        o = _mla_sdpa(cfg, qn, qr, kn, k_rope, v, posv[0], cpos,
                      window=cfg.sliding_window)
    else:
        wkv_b = p["wkv_b"].reshape(r, H, dn + dv)
        w_uk, w_uv = wkv_b[..., :dn], wkv_b[..., dn:]      # (r,H,dn),(r,H,dv)
        q_lat = jnp.einsum("bqhd,rhd->bqhr", qn, w_uk,
                           preferred_element_type=jnp.float32
                           ).astype(qn.dtype)               # (B,1,H,r)
        s = (jnp.einsum("bqhr,bsr->bhqs", q_lat, c_kv,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhd,bsd->bhqs", qr, k_rope,
                          preferred_element_type=jnp.float32)) * scale
        mask = (cpos >= 0) & (cpos <= pos)
        if cfg.sliding_window:
            mask = mask & (cpos > pos - cfg.sliding_window)
        s = jnp.where(mask[None, None, None, :], s, -1e30)
        pa = jax.nn.softmax(s, axis=-1).astype(c_kv.dtype)
        o_lat = jnp.einsum("bhqs,bsr->bqhr", pa, c_kv,
                           preferred_element_type=jnp.float32
                           ).astype(c_kv.dtype)
        o = jnp.einsum("bqhr,rhd->bqhd", o_lat, w_uv,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    y = o.reshape(B, 1, -1) @ p["wo"]
    return y, {"c_kv": c_kv, "k_rope": k_rope, "positions": cpos}
