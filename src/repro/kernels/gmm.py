"""Drop-free routed experts: sort the (token, choice) pairs by expert, one
grouped matmul per expert projection over the experts held here, scale by
the gate weight and scatter-add back.

``routed_experts(x, gid, w, gate, up, down)`` takes

- ``x`` (T, d): the tokens;
- ``gid`` (T, k) int: each choice's index among the G held experts, or G
  where the chosen expert is not held here (it adds nothing);
- ``w`` (T, k): each choice's gate weight;
- ``gate``, ``up`` (G, d, f) and ``down`` (G, f, d): the held experts,

and returns (T, d), the held experts' part of the layer's output. Sorting
puts the held choices first, grouped by expert, so the grouped matmul
computes only rows routed to an expert held here: no capacity, no drops.

The grouped matmul is ``jax.lax.ragged_dot`` on every platform. On a TPU
the compiler lowers it to a grouped kernel (``ragged-dot-none`` in the
compiled program and the device trace) whose grid covers only the rows
the group sizes name, and leaves the other rows of its result unwritten.
The TPU lowering takes no batch dimension, so the layer is wrapped to fold
a vmapped cohort into one problem: the cohort's tokens side by side and
its rows' experts as separate groups (``custom_vmap``), with a
``custom_vjp`` whose backward is batched the same way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import custom_batching


def _forward(x, gid, w, gate, up, down):
    """The layer for one problem; see the module docstring."""
    k = gid.shape[1]
    n_groups = gate.shape[0]
    with jax.named_scope("moe.route"):
        flat = gid.reshape(-1)
        order = jnp.argsort(flat, stable=True)   # held choices first, by expert
        tok = order // k
        held = flat[order] < n_groups
        sizes = jnp.bincount(flat, length=n_groups + 1)[:n_groups].astype(jnp.int32)
        # the rows past the groups are left unwritten by the TPU's grouped
        # kernel, in its result and in its transpose (the rows' gradient):
        # masked here, so neither reaches the tokens or their gradient
        xs = jnp.where(held[:, None], x[tok], 0)
    with jax.named_scope("moe.experts"):
        h = jax.nn.silu(jax.lax.ragged_dot(xs, gate, sizes)) * jax.lax.ragged_dot(xs, up, sizes)
        ys = jax.lax.ragged_dot(h, down, sizes)
    with jax.named_scope("moe.combine"):
        ws = jnp.where(held, w.reshape(-1)[order], 0).astype(ys.dtype)
        ys = jnp.where(held[:, None], ys, 0) * ws[:, None]
        return jnp.zeros_like(x).at[tok].add(ys)


# ------------------------------------------------------------ under vmap


def _fold_cohort(axis_size, in_batched, x, gid, w, gate, up, down):
    """A vmapped call as one problem: tokens of every cohort row side by
    side, and row b's held expert g as group b * G + g (not held: B * G)."""
    args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, b in zip((x, gid, w, gate, up, down), in_batched)]
    x, gid, w, gate, up, down = args
    n_groups = gate.shape[1]
    offset = (jnp.arange(axis_size) * n_groups)[:, None, None]
    gid = jnp.where(gid < n_groups, gid + offset, axis_size * n_groups)
    return [a.reshape((-1,) + a.shape[2:]) for a in (x, gid, w, gate, up, down)]


def _unfold(axis_size, a):
    return a.reshape((axis_size, -1) + a.shape[1:])


def _backward(x, gid, w, gate, up, down, gy):
    _, vjp = jax.vjp(lambda x, w, gate, up, down: _forward(x, gid, w, gate, up, down),
                     x, w, gate, up, down)
    return vjp(gy)


_forward_cv = custom_batching.custom_vmap(_forward)
_backward_cv = custom_batching.custom_vmap(_backward)


@_forward_cv.def_vmap
def _forward_rule(axis_size, in_batched, *args):
    y = _forward(*_fold_cohort(axis_size, in_batched, *args))
    return _unfold(axis_size, y), True


@_backward_cv.def_vmap
def _backward_rule(axis_size, in_batched, *args):
    folded = _fold_cohort(axis_size, in_batched[:6], *args[:6])
    gy = args[6] if in_batched[6] else jnp.broadcast_to(args[6], (axis_size,) + args[6].shape)
    grads = _backward(*folded, gy.reshape((-1,) + gy.shape[2:]))
    return tuple(_unfold(axis_size, g) for g in grads), (True,) * len(grads)


@jax.custom_vjp
def routed_experts(x, gid, w, gate, up, down):
    """See the module docstring."""
    return _forward_cv(x, gid, w, gate, up, down)


def _vjp_fwd(x, gid, w, gate, up, down):
    # the backward recomputes the forward from the inputs: residuals in
    # the sorted layout of a folded cohort would not split by row
    return _forward_cv(x, gid, w, gate, up, down), (x, gid, w, gate, up, down)


def _vjp_bwd(res, gy):
    dx, dw, dgate, dup, ddown = _backward_cv(*res, gy)
    return dx, None, dw, dgate, dup, ddown


routed_experts.defvjp(_vjp_fwd, _vjp_bwd)
