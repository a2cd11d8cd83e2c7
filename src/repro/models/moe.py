"""Mixture-of-Experts FFN: top-k router, shared experts, and two dispatches.

Drop-free (``capacity_factor == 0``, deepseek-v2): every (token, choice)
pair routed to an expert held here is computed. The pairs are sorted by
expert and each expert projection is one grouped matmul over the held
experts (``kernels.routed_experts``). ``experts_held`` names the routed
experts this chip holds under expert parallelism: the router scores all of
them, and what the absent experts would add is left to the chips that hold
them (on one chip the layer runs without that exchange).

Capacity (``capacity_factor > 0``): dispatch is *grouped*: tokens are split
into ``moe_groups`` groups (set to the data-parallel degree at launch so
each group is local to one mesh row — the standard per-device "dropping"
implementation). Within each group every expert picks its top-C tokens by
gate weight (C = n*k/E * capacity_factor); tokens beyond capacity are
dropped (identity + shared experts still apply). This keeps dispatch fully
vectorised (no sorting, no dynamic shapes) with honest FLOPs:
E * C * d * ff ~= n * k * capacity_factor * d * ff.

Expert weights are stacked (E, d, ff) so the launcher can shard E over the
``model`` mesh axis (expert parallelism, deepseek) or ff (qwen2-moe).

The load-balance loss is switch-style (``aux_loss="switch"``) or
DeepSeek-V2's per-sequence one (``"seq"``), weighted by ``aux_weight``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import routed_experts
from repro.models.layers import dtype_of, normal


def init_moe(key, cfg):
    dt = dtype_of(cfg)
    d, f = cfg.d_model, cfg.moe_d_ff
    E = cfg.padded_experts            # dummy experts (if any) masked below
    lo, hi = cfg.held_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": normal(ks[0], (d, E), d ** -0.5, jnp.float32),
        "gate": normal(ks[1], (hi - lo, d, f), d ** -0.5, dt),
        "up": normal(ks[2], (hi - lo, d, f), d ** -0.5, dt),
        "down": normal(ks[3], (hi - lo, f, d), f ** -0.5, dt),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "gate": normal(k1, (d, fs), d ** -0.5, dt),
            "up": normal(k2, (d, fs), d ** -0.5, dt),
            "down": normal(k3, (fs, d), fs ** -0.5, dt),
        }
    return p


def capacity(n_tokens_per_group: int, cfg) -> int:
    c = math.ceil(n_tokens_per_group * cfg.top_k / cfg.n_experts
                  * cfg.capacity_factor)
    return min(n_tokens_per_group, max(8, c))


def _route(p, cfg, x):
    """Softmax router over every expert, then greedy top-k. x (..., d) ->
    probs (..., E), top-k weights and expert ids (..., k). The logits are
    float32 at full precision, as the published models compute them."""
    E = cfg.padded_experts
    logits = jnp.matmul(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if E > cfg.n_experts:             # mask padded (dummy) experts
        pad_mask = jnp.arange(E) >= cfg.n_experts
        logits = jnp.where(pad_mask, -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return probs, topv, topi


def _aux_loss(cfg, probs, topi):
    """probs (B, S, E), topi (B, S, k) -> the load-balance loss."""
    E = probs.shape[-1]
    if cfg.aux_loss == "seq":
        # DeepSeek-V2: alpha * mean_b sum_j (count_bj / (S k / E)) * mean_s P_bsj
        S, k = topi.shape[1], topi.shape[2]
        count = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32), axis=(1, 2))
        return jnp.mean(jnp.sum(count / (S * k / E) * jnp.mean(probs, axis=1), -1))
    # switch-style
    frac_tokens = jnp.mean(jax.nn.one_hot(topi[..., 0], E), axis=(0, 1))
    mean_prob = jnp.mean(probs, axis=(0, 1))
    return E * jnp.sum(frac_tokens * mean_prob)


def _shared(p, x):
    sp = p["shared"]
    return (jax.nn.silu(x @ sp["gate"]) * (x @ sp["up"])) @ sp["down"]


def moe_ffn(p, cfg, x, groups: int = 1):
    """x: (B, S, d) -> (y, aux_loss). groups must divide B*S."""
    if cfg.capacity_factor <= 0:
        return _drop_free_ffn(p, cfg, x)
    Bsz, S, d = x.shape
    E, k = cfg.padded_experts, cfg.top_k
    N = Bsz * S
    G = groups
    n = N // G
    C = capacity(n, cfg)
    xf = x.reshape(G, n, d)

    probs, topv, topi = _route(p, cfg, xf)                     # (G,n,E|k)
    gates = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32)
                    * topv[..., None], axis=2)                 # (G,n,E)

    # per-expert top-C tokens within each group
    w_sel, idx = jax.lax.top_k(gates.swapaxes(1, 2), C)        # (G,E,C)
    flat_idx = idx.reshape(G, E * C)
    xs = jnp.take_along_axis(xf, flat_idx[..., None], axis=1)  # (G,E*C,d)
    xs = xs.reshape(G, E, C, d)

    h = (jax.nn.silu(jnp.einsum("gecd,edf->gecf", xs, p["gate"]))
         * jnp.einsum("gecd,edf->gecf", xs, p["up"]))
    ye = jnp.einsum("gecf,efd->gecd", h, p["down"])
    ye = ye * w_sel[..., None].astype(ye.dtype)

    out = jnp.zeros((G, n, d), ye.dtype)
    out = jax.vmap(lambda o, i, y: o.at[i].add(y))(
        out, flat_idx, ye.reshape(G, E * C, d))

    if cfg.n_shared_experts:
        out = out + _shared(p, xf)
    aux = _aux_loss(cfg, probs.reshape(Bsz, S, E), topi.reshape(Bsz, S, k))
    return out.reshape(Bsz, S, d), aux


def _drop_free_ffn(p, cfg, x):
    """Every choice routed to a held expert is computed."""
    Bsz, S, d = x.shape
    lo, hi = cfg.held_experts
    xf = x.reshape(Bsz * S, d)
    with jax.named_scope("moe.route"):
        probs, topv, topi = _route(p, cfg, xf)
        gid = jnp.where((topi >= lo) & (topi < hi), topi - lo, hi - lo)
    out = routed_experts(xf, gid, topv.astype(x.dtype), p["gate"], p["up"], p["down"])
    if cfg.n_shared_experts:
        out = out + _shared(p, xf)
    E, k = probs.shape[-1], topi.shape[-1]
    aux = _aux_loss(cfg, probs.reshape(Bsz, S, E), topi.reshape(Bsz, S, k))
    return out.reshape(Bsz, S, d), aux
