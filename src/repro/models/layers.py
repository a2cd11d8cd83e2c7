"""Shared pure-JAX building blocks (no flax): norms, MLPs, RoPE, embeddings.

Parameters are plain nested dicts of jnp arrays. ``init_*`` functions return
param dicts; apply functions are pure. Layer stacks are built by vmapping the
per-layer init over a key axis so params arrive pre-stacked for lax.scan.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(cfg):
    return jnp.dtype(cfg.param_dtype)


def normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, dtype=jnp.float32)).astype(dtype)


def rms_norm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def linear(x, w, b=None):
    y = x @ w
    if b is not None:
        y = y + b
    return y


# ---------------------------------------------------------------- MLPs

def init_swiglu(key, d_model, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    std = d_model ** -0.5
    return {
        "gate": normal(k1, (d_model, d_ff), std, dtype),
        "up": normal(k2, (d_model, d_ff), std, dtype),
        "down": normal(k3, (d_ff, d_model), d_ff ** -0.5, dtype),
    }


def swiglu(p, x):
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def init_gelu_mlp(key, d_model, d_ff, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "fc1": normal(k1, (d_model, d_ff), d_model ** -0.5, dtype),
        "b1": jnp.zeros((d_ff,), dtype),
        "fc2": normal(k2, (d_ff, d_model), d_ff ** -0.5, dtype),
        "b2": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(p, x):
    return linear(jax.nn.gelu(linear(x, p["fc1"], p["b1"])), p["fc2"], p["b2"])


# ---------------------------------------------------------------- RoPE

def yarn_of(cfg):
    """The config where it scales its rope by yarn, else None."""
    return cfg if cfg.yarn_factor > 1 else None


def yarn_mscale(factor, mscale):
    """Yarn's attention temperature term (DeepSeek-V2's yarn_get_mscale)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_bounds(head_dim, theta, yarn):
    """(low, high) rotary dims of yarn's ramp: floor/ceil of the dim whose
    wavelength turns ``beta_fast`` / ``beta_slow`` times over the original
    context."""
    def dim(turns):
        return (head_dim * math.log(yarn.yarn_original_max_pos / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(dim(yarn.yarn_beta_fast)), 0),
            min(math.ceil(dim(yarn.yarn_beta_slow)), head_dim - 1))


def rope_freqs(head_dim, theta, yarn=None):
    freqs = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    if yarn is None:
        return freqs
    # yarn: the low-frequency dims (past ``high``) interpolate by the
    # factor, the high-frequency ones (before ``low``) keep their speed
    low, high = yarn_bounds(head_dim, theta, yarn)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0, 1)
    return (freqs / yarn.yarn_factor * ramp + freqs * (1 - ramp)).astype(np.float32)


def apply_rope(x, positions, theta, yarn=None):
    """x: (..., S, H, hd) or (..., S, hd); positions: (..., S). ``yarn``:
    a config with yarn scaling (``yarn_of``), or None for plain rope."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta, yarn))     # (hd/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, hd/2)
    if x.ndim == ang.ndim + 1:                            # has a heads axis
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        m = (yarn_mscale(yarn.yarn_factor, yarn.yarn_mscale)
             / yarn_mscale(yarn.yarn_factor, yarn.yarn_mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n_pos, d_model):
    pos = np.arange(n_pos)[:, None]
    dim = np.arange(d_model)[None, :]
    ang = pos / np.power(10_000, 2 * (dim // 2) / d_model)
    enc = np.where(dim % 2 == 0, np.sin(ang), np.cos(ang))
    return jnp.asarray(enc, dtype=jnp.float32)


# ---------------------------------------------------------------- embedding

def init_embedding(key, vocab, d_model, dtype):
    return {"tok": normal(key, (vocab, d_model), 0.02, dtype)}


def embed(p, tokens):
    return jnp.take(p["tok"], tokens, axis=0)


def unembed(p, x, head=None):
    w = head if head is not None else p["tok"].T
    return x @ w


def stacked_init(init_fn, key, n, *args, **kwargs):
    """vmap a per-layer init over n keys -> params with leading layer axis."""
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_fn(k, *args, **kwargs))(keys)


def cross_entropy(logits, labels, mask=None, vocab=None):
    """Mean CE over valid positions. logits (..., V) fp32-cast; labels int."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
