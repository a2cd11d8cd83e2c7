"""Plain reference for the benchmark's `correct`: the federated steps the
cells run (local SGD, the FedAvg round, the fused AdamW step, the FedAST
FedAdam flush), over a model family's loss, written in straightforward
`jax.numpy`.

A model family is a module of its own, ``families/<name>.py``, found by
``family(cfg)`` from the configuration file's ``family`` key (``dense``
where it has none): it gives the weights' shapes and scales, the plain
forward pass and loss, the system's ``ModelConfig`` and the counts (see
``families/dense.py``). A new architecture arrives as a new family file.

It imports nothing of the system under test (a family's
``model_config``, which only ``cell.py`` calls, imports the system's
``ModelConfig`` when called). Weights come from
``make_weights_fn`` (made here from the seed, in the layout the system
trains), and batches are the token rows the timed path was fed.

Precision: float32 with `highest` matmul precision for the reference,
or `bfloat16` throughout (params, activations and updates) for the
control that a correct run must be told apart from.

Departures from the published models, each one the system's own and
shared by every family through ``weighted_nll``:
- labels are the input tokens themselves (no shift): position i is
  scored on the token at position i;
- the vocabulary rows are padded to a multiple of 256 and the padding
  columns take part in the softmax;
- the loss is the mean over positions weighted by each row's client
  weight, ``sum(w_r * nll) / max(sum(w_r) * seq, 1)``.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import math
import re
from contextlib import nullcontext
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

FAMILIES = Path(__file__).resolve().parent / "families"
F32 = jnp.float32
VOCAB_PAD = 256


def padded_vocab(vocab: int) -> int:
    return -(-vocab // VOCAB_PAD) * VOCAB_PAD


# ------------------------------------------------------------------ families


def family(cfg: dict):
    """The model family module of a configuration: ``families/<name>.py``
    for the file's ``family`` key, ``dense`` where it has none. Raises
    LookupError, naming the families there, for one that is not."""
    return _family(cfg.get("family", "dense"))


@functools.lru_cache(maxsize=None)
def _family(name: str):
    path = FAMILIES / f"{name}.py"
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", name) or not path.is_file():
        have = sorted(f.stem for f in FAMILIES.glob("*.py") if not f.stem.startswith("_"))
        raise LookupError(f"no model family {name!r} in {FAMILIES.name}/; have {have}")
    spec = importlib.util.spec_from_file_location(f"family_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------ weights


def make_weights_fn(cfg: dict):
    """A jitted ``seed_words -> params`` that makes every weight on the
    device in one call: the family's gains are ones, matrices are normal
    with its fan-in scale. ``seed_words`` is a uint32[2] from ``seed_key``."""
    fam = family(cfg)
    flat, treedef = jax.tree.flatten_with_path(
        fam.weight_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in flat]

    @jax.jit
    def make(words):
        key = jax.random.wrap_key_data(words)
        keys = jax.random.split(key, len(flat))
        out = []
        for k, path, (p, shape) in zip(keys, paths, flat):
            if p[-1].key in fam.GAINS:
                out.append(jnp.ones(shape, F32))
            else:
                out.append(fam.init_std(path, cfg) * jax.random.normal(k, shape, F32))
        return jax.tree.unflatten(treedef, out)

    return make


def seed_key(seed: int) -> np.ndarray:
    """Any non-negative seed (64 bits and more) to a threefry key's data."""
    s = int(seed) & (2**64 - 1)
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


# ------------------------------------------------------------------ shared layers


def _rms(x, scale, eps):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(x.dtype)


def _rope(x, theta):
    """Rotate-half RoPE at positions 0..S-1; x is (B, S, heads, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).astype(x.dtype)


def weighted_nll(logits, tokens, row_w=None):
    """The system's loss from float32 logits (B, S, V): each position
    scored on its own token, the mean weighted by each row's weight."""
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, tokens[..., None], -1)[..., 0]
    w = jnp.ones(tokens.shape[0], F32) if row_w is None else row_w.astype(F32)
    mask = jnp.broadcast_to(w[:, None], nll.shape)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ------------------------------------------------------------------ steps


class Reference:
    """The federated steps of the cells over the family's loss, at one
    precision.

    ``dtype`` float32 runs at `highest` matmul precision; bfloat16 casts
    params, activations and updates to bfloat16 (the control)."""

    def __init__(self, cfg: dict, dtype=F32):
        self.cfg = cfg
        self.dtype = jnp.dtype(dtype)
        loss_fn = family(cfg).loss_fn
        self._vg = jax.jit(jax.value_and_grad(
            lambda p, t, w: loss_fn(p, cfg, t, w)))

    def _ctx(self):
        return jax.default_matmul_precision("highest") if self.dtype == F32 else nullcontext()

    def cast(self, tree):
        return jax.tree.map(lambda a: a.astype(self.dtype), tree)

    def value_and_grad(self, params, tokens, row_w=None):
        with self._ctx():
            return self._vg(params, jnp.asarray(tokens), None if row_w is None
                            else jnp.asarray(row_w, self.dtype))

    def sgd_local(self, params, tokens, tau, lr):
        """tau SGD steps of one client on its rows; (params, mean loss)."""
        losses = []
        for _ in range(tau):
            loss, g = self.value_and_grad(params, tokens)
            params = jax.tree.map(lambda p, gg: (p - lr * gg).astype(p.dtype), params, g)
            losses.append(float(loss))
        return params, float(np.mean(losses))

    def fedavg_round(self, params, rows, row_w, tau, lr):
        """Each row trains alone from ``params``; the new params are the
        row-weighted mean. Returns (new params, per-row losses)."""
        w = np.asarray(row_w, np.float64)
        w = w / w.sum()
        acc = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
        losses = []
        for r in range(len(rows)):
            pr, loss = self.sgd_local(params, rows[r:r + 1], tau, lr)
            acc = jax.tree.map(lambda a, p, wr=float(w[r]): a + wr * p.astype(F32), acc, pr)
            losses.append(loss)
        return self.cast(acc), np.asarray(losses)

    def adamw_init(self, params):
        z = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
        return {"mu": z, "nu": z, "count": 0}

    def adamw_step(self, params, state, tokens, row_w, opt, block=2):
        """One weighted-batch AdamW step with global-norm clipping and
        bias correction. Returns (params, state, loss, clipped grads).

        The weighted loss and its gradient are summed over blocks of
        ``block`` rows, each weighted by its share of the row weights, so
        that the vocabulary-wide logits of only a few rows are live."""
        w = np.asarray(row_w, np.float64)
        loss, g = 0.0, None
        for b in range(0, len(tokens), block):
            share = float(w[b:b + block].sum() / w.sum())
            lb, gb = self.value_and_grad(params, tokens[b:b + block], w[b:b + block])
            loss += share * float(lb)
            gb = jax.tree.map(lambda x, s=share: s * x.astype(F32), gb)
            g = gb if g is None else jax.tree.map(jnp.add, g, gb)
        gn = math.sqrt(sum(float(jnp.sum(x * x)) for x in jax.tree.leaves(g)))
        if opt["max_grad_norm"]:
            scale = min(1.0, opt["max_grad_norm"] / max(gn, 1e-9))
            g = jax.tree.map(lambda x: x * scale, g)
        c = state["count"] + 1
        b1, b2 = opt["b1"], opt["b2"]
        bc1, bc2 = 1.0 - b1 ** c, 1.0 - b2 ** c

        def upd(p, m, n, x):
            m = b1 * m + (1 - b1) * x
            n = b2 * n + (1 - b2) * x * x
            step = (m / bc1) / (jnp.sqrt(n / bc2) + opt["eps"])
            step = step + opt["weight_decay"] * p.astype(F32)
            return (p.astype(F32) - opt["lr"] * step).astype(p.dtype), m, n

        # leaf by leaf, each old leaf dropped as its new one is made, so
        # that two copies of the params and moments are never live
        leaves, treedef = jax.tree.flatten(params)
        mus, nus = jax.tree.leaves(state["mu"]), jax.tree.leaves(state["nu"])
        gs = jax.tree.leaves(g)
        del params, state
        for i in range(len(leaves)):
            leaves[i], mus[i], nus[i] = upd(leaves[i], mus[i], nus[i], gs[i])
        return (jax.tree.unflatten(treedef, leaves),
                {"mu": jax.tree.unflatten(treedef, mus), "nu": jax.tree.unflatten(treedef, nus),
                 "count": c}, float(loss), g)

    def fedadam_flush(self, params, state, deltas, weights, staleness, beta, srv):
        """FedAST flush with a FedAdam server: staleness-discounted mean of
        client deltas over the undiscounted weight sum, then Adam moments
        (v0 = eps^2, no bias correction). Returns (params, state, d)."""
        w = np.asarray(weights, np.float64)
        disc = w * (1.0 + np.asarray(staleness, np.float64)) ** (-beta) / w.sum()
        d = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
        for dk, ck in zip(deltas, disc):
            d = jax.tree.map(lambda a, x, c=float(ck): a + c * x.astype(F32), d, dk)
        b1, b2, eps, lr = srv["beta1"], srv["beta2"], srv["eps"], srv["lr"]
        m = jax.tree.map(lambda m_, x: b1 * m_ + (1 - b1) * x, state["m"], d)
        v = jax.tree.map(lambda v_, x: b2 * v_ + (1 - b2) * x * x, state["v"], d)
        params = jax.tree.map(
            lambda p, m_, v_: (p.astype(F32) + srv["server_lr"] * lr * m_
                               / (jnp.sqrt(v_) + eps)).astype(p.dtype), params, m, v)
        return params, {"m": m, "v": v}, d

    def fedadam_init(self, params, eps):
        return {"m": jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params),
                "v": jax.tree.map(lambda p: jnp.full(p.shape, eps * eps, F32), params)}


@functools.lru_cache(maxsize=8)
def _cached(kind: str, cfg_json: str, dtype: str):
    cfg = json.loads(cfg_json)
    return make_weights_fn(cfg) if kind == "weights" else Reference(cfg, dtype)


def weights_fn(cfg: dict):
    """``make_weights_fn``, one jitted function per configuration."""
    return _cached("weights", json.dumps(cfg, sort_keys=True), "")


def reference_for(cfg: dict, dtype=F32) -> Reference:
    """A ``Reference`` per configuration and precision, so that its jitted
    gradient compiles once per process."""
    return _cached("reference", json.dumps(cfg, sort_keys=True), jnp.dtype(dtype).name)
