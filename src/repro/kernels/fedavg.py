"""Pallas TPU kernels for the MMFL server aggregation (Alg. 1 line 12).

w_s <- sum_k p_{k,Sel} * w_{k,s}: a weighted reduction over the client axis
of the stacked cohort parameters. At datacenter scale this is the paper's
per-round hot spot on the server (K x N parameter bytes streamed once).

Grid (cdiv(N, blk),) with block (K, blk): each step loads a (K, blk) tile
of the stacked params into VMEM plus the (1, K) weight row, and emits the
(1, blk) weighted column sum via a single MXU matvec. HBM traffic = K*N
reads + N writes, the streaming optimum: the last block is ragged rather
than the cohort padded, and ``fedavg_block`` sizes the tile from K so the
per-step overhead stays small beside the bytes each step moves.

``fused_aggregate_pallas`` extends the same tiling to the async flush hot
path (FedAST): staleness-discount + weighted-reduce + server-optimizer
(momentum/adam/yogi) moment update in ONE pass over the stacked cohort
deltas — the unfused path streams the K x N deltas once for the reduce
and the N-sized moments twice more per optimizer op; fused, every tensor
is touched exactly once per flush.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 2048

# fedavg tiling: each grid step should move about _STEP_BYTES of the f32
# cohort, within _VMEM_BUDGET of the chip's scoped VMEM (16 MiB on v5e)
_STEP_BYTES = 1 << 20
_VMEM_BUDGET = 12 << 20
_LANES, _SUBLANES = 128, 8

# fused-kernel scalar row: [beta, inv_norm, lr, beta1, beta2, eps] padded
# to one 128-lane f32 tile so the block shape meets the TPU minimum
_N_SCALARS = 128
FUSED_MODES = ("fedavg", "fedavgm", "fedadam", "fedyogi")


def _fedavg_kernel(w_ref, x_ref, o_ref):
    w = w_ref[...]                                 # (1, K)
    x = x_ref[...]                                 # (K, blk)
    # HIGHEST: an f32 average of absolute params must not round them to
    # bf16 on the MXU
    o_ref[...] = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def fedavg_block(k: int) -> int:
    """The fedavg kernel's parameter tile for a K-row f32 cohort: a multiple
    of 128 lanes that moves about ``_STEP_BYTES`` of the cohort a grid step,
    capped so the double-buffered (K, blk) input and (1, blk) output tiles,
    each padded to whole 8-row sublane tiles, fit ``_VMEM_BUDGET``."""
    rows = -(-k // _SUBLANES) * _SUBLANES + _SUBLANES      # input + output
    cap = _VMEM_BUDGET // (2 * rows * 4) // _LANES * _LANES
    want = -(-_STEP_BYTES // (4 * k * _LANES)) * _LANES
    return max(_LANES, min(want, cap))


def fedavg_pallas(stacked, weights, *, blk=None, interpret=None):
    """stacked: (K, N) flat cohort params; weights: (K,) normalised.

    Returns (N,) the weighted average (weights are used as given — callers
    normalise; see fed/server.py). ``blk=None`` (the default) takes the
    tile from K (``fedavg_block``).

    ``interpret=None`` (the default) auto-selects from the JAX platform:
    compiled on TPU/GPU, interpreter (the Python-level oracle) on CPU —
    so callers get the fast path wherever one exists without having to
    thread platform knowledge through.
    """
    stacked = jnp.asarray(stacked)
    weights = jnp.asarray(weights)
    if stacked.ndim != 2:
        raise ValueError(
            f"fedavg_pallas: stacked must be (K, N) flat cohort params, "
            f"got shape {stacked.shape}")
    if weights.ndim != 1 or weights.shape[0] != stacked.shape[0]:
        raise ValueError(
            f"fedavg_pallas: weights must be ({stacked.shape[0]},) to "
            f"match the cohort axis of stacked {stacked.shape}, got "
            f"{weights.shape}")
    if not (jnp.issubdtype(stacked.dtype, jnp.floating)
            and jnp.issubdtype(weights.dtype, jnp.floating)):
        raise TypeError(
            f"fedavg_pallas: floating-point inputs required, got "
            f"stacked={stacked.dtype}, weights={weights.dtype}")
    # mixed-precision cohorts (e.g. bf16 deltas + f32 weights): PROMOTE to
    # the common dtype for the kernel — demoting the normalised weights to
    # bf16 (the pre-fix behaviour) rounds them before the matvec — and
    # cast the result back to the cohort dtype
    out_dtype = stacked.dtype
    common = jnp.promote_types(stacked.dtype, weights.dtype)
    stacked = stacked.astype(common)
    weights = weights.astype(common)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _fedavg_jit(stacked, weights, blk=blk,
                       interpret=interpret).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("blk", "interpret"))
def _fedavg_jit(stacked, weights, *, blk, interpret):
    K, N = stacked.shape
    blk = min(fedavg_block(K) if blk is None else blk, N)
    # no pad: the last block is ragged. Its out-of-bounds lanes read
    # unspecified values and their writes are dropped; each output lane
    # reads only its own input lane, so the kept lanes are exact
    out = pl.pallas_call(
        _fedavg_kernel,
        grid=(pl.cdiv(N, blk),),
        in_specs=[
            pl.BlockSpec((1, K), lambda i: (0, 0)),
            pl.BlockSpec((K, blk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), stacked.dtype),
        interpret=interpret,
    )(weights[None, :], stacked)
    return out[0]


# ------------------------------------------------- fused async aggregation


def _fused_kernel(w_ref, s_ref, c_ref, x_ref, m_ref, v_ref,
                  o_ref, om_ref, ov_ref, *, mode):
    w = w_ref[...]                                 # (1, K) base weights
    st = s_ref[...]                                # (1, K) staleness
    c = c_ref[...]                                 # (1, _N_SCALARS)
    beta, inv_norm, lr = c[0, 0], c[0, 1], c[0, 2]
    b1, b2, eps = c[0, 3], c[0, 4], c[0, 5]
    # FedAST discount folded with the (undiscounted-sum) normalisation:
    # exp/log form of (1+s)^-beta, staleness >= 0 so log1p is safe
    disc = w * jnp.exp(-beta * jnp.log1p(st)) * inv_norm
    x = x_ref[...]                                 # (K, blk) delta tile
    d = jax.lax.dot_general(
        disc, x, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)        # (1, blk)
    if mode == "fedavg":
        o_ref[...] = lr * d
        om_ref[...] = m_ref[...]
        ov_ref[...] = v_ref[...]
    elif mode == "fedavgm":
        m = b1 * m_ref[...] + d
        o_ref[...] = lr * m
        om_ref[...] = m
        ov_ref[...] = v_ref[...]
    else:                                          # fedadam | fedyogi
        m = b1 * m_ref[...] + (1.0 - b1) * d
        d2 = d * d
        if mode == "fedadam":
            v = b2 * v_ref[...] + (1.0 - b2) * d2
        else:
            v0 = v_ref[...]
            v = v0 - (1.0 - b2) * d2 * jnp.sign(v0 - d2)
        o_ref[...] = lr * m / (jnp.sqrt(v) + eps)
        om_ref[...] = m
        ov_ref[...] = v


def fused_aggregate_pallas(stacked, weights, staleness, m, v, *, mode,
                           beta, normalizer, lr=1.0, beta1=0.9,
                           beta2=0.99, eps=1e-3, blk=DEFAULT_BLOCK,
                           interpret=None):
    """One-pass async flush: staleness-discounted weighted reduce of the
    (K, N) stacked cohort deltas + server-optimizer moment update.

    stacked: (K, N) client deltas; weights/staleness: (K,); m/v: (N,)
    f32 server moments (pass zeros for modes that ignore them). ``mode``
    is one of ``FUSED_MODES``; beta/normalizer/lr/beta1/beta2/eps ride
    in a scalar row so per-flush normalizer changes never recompile.
    Everything computes in f32. Returns ``(update, new_m, new_v)``,
    each (N,) f32. ``interpret=None`` auto-selects like fedavg_pallas.
    """
    if mode not in FUSED_MODES:
        raise ValueError(
            f"fused_aggregate_pallas: unknown mode {mode!r}; "
            f"valid: {', '.join(FUSED_MODES)}")
    stacked = jnp.asarray(stacked, jnp.float32)
    if stacked.ndim != 2:
        raise ValueError(
            f"fused_aggregate_pallas: stacked must be (K, N), got "
            f"shape {stacked.shape}")
    K, N = stacked.shape
    weights = jnp.asarray(weights, jnp.float32)
    staleness = jnp.asarray(staleness, jnp.float32)
    for nm, a in (("weights", weights), ("staleness", staleness)):
        if a.shape != (K,):
            raise ValueError(
                f"fused_aggregate_pallas: {nm} must be ({K},) to match "
                f"the cohort axis of stacked {stacked.shape}, got "
                f"{a.shape}")
    m = jnp.asarray(m, jnp.float32)
    v = jnp.asarray(v, jnp.float32)
    for nm, a in (("m", m), ("v", v)):
        if a.shape != (N,):
            raise ValueError(
                f"fused_aggregate_pallas: {nm} must be ({N},) to match "
                f"the parameter axis of stacked {stacked.shape}, got "
                f"{a.shape}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    inv = 1.0 / jnp.maximum(jnp.asarray(normalizer, jnp.float32), 1e-12)
    sc = jnp.zeros(_N_SCALARS, jnp.float32)
    sc = sc.at[0].set(jnp.asarray(beta, jnp.float32)).at[1].set(inv)
    sc = sc.at[2].set(lr).at[3].set(beta1).at[4].set(beta2).at[5].set(eps)
    return _fused_jit(stacked, weights, staleness, sc, m, v, mode=mode,
                      blk=blk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("mode", "blk", "interpret"))
def _fused_jit(stacked, weights, staleness, scalars, m, v, *, mode, blk,
               interpret):
    K, N = stacked.shape
    blk = min(blk, N)
    pad = (-N) % blk
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
        m = jnp.pad(m, (0, pad))
        v = jnp.pad(v, (0, pad))
    Np = N + pad
    row = pl.BlockSpec((1, blk), lambda i: (0, i))
    out, new_m, new_v = pl.pallas_call(
        functools.partial(_fused_kernel, mode=mode),
        grid=(Np // blk,),
        in_specs=[
            pl.BlockSpec((1, K), lambda i: (0, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
            pl.BlockSpec((1, _N_SCALARS), lambda i: (0, 0)),
            pl.BlockSpec((K, blk), lambda i: (0, i)),
            row,
            row,
        ],
        out_specs=[row, row, row],
        out_shape=[jax.ShapeDtypeStruct((1, Np), jnp.float32)] * 3,
        interpret=interpret,
    )(weights[None, :], staleness[None, :], scalars[None, :], stacked,
      m[None, :], v[None, :])
    return out[0, :N], new_m[0, :N], new_v[0, :N]
