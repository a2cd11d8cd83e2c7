"""Jit'd public wrappers for the Pallas kernels.

On TPU backends the kernels compile natively (interpret=False); on the CPU
container they execute via interpret=True, which runs the kernel body in
Python for correctness validation (see tests/test_kernels.py). The model
code's pure-jnp paths remain the default for dry-run lowering — the wrappers
here are the deployment path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fedavg import fedavg_pallas, fused_aggregate_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import gated_rmsnorm_pallas, rmsnorm_pallas
from repro.kernels.ssm_scan import ssd_scan_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal=True, blk_q=128, blk_k=128):
    """q: (B,H,Sq,hd); k,v: (B,KV,Sk,hd) -> (B,H,Sq,hd)."""
    return flash_attention_pallas(q, k, v, causal=causal, blk_q=blk_q,
                                  blk_k=blk_k, interpret=not _on_tpu())


def ssd_scan(x, a, b, c, *, chunk=128):
    """Chunked SSD scan; see kernels/ssm_scan.py for the contract."""
    return ssd_scan_pallas(x, a, b, c, chunk=chunk,
                           interpret=not _on_tpu())


def fedavg_aggregate(stacked, weights, *, blk=None):
    """Weighted client-parameter aggregation (MMFL server, Alg. 1 l.12).
    Interpret mode auto-selects from the platform, and ``blk=None`` takes
    the kernel's tile from the cohort size (see fedavg_pallas).
    Mixed-precision cohorts (bf16 deltas, f32 weights) are promoted to
    the common dtype for the kernel and cast back on return."""
    return fedavg_pallas(stacked, weights, blk=blk)


@functools.partial(jax.jit, static_argnames=("mode",))
def _fused_ref_jit(stacked, weights, staleness, m, v, beta, normalizer,
                   lr, beta1, beta2, eps, *, mode):
    from repro.kernels.ref import ref_fused_aggregate

    return ref_fused_aggregate(
        stacked, weights, staleness, m, v, mode=mode, beta=beta,
        normalizer=normalizer, lr=lr, beta1=beta1, beta2=beta2, eps=eps)


def fused_aggregate(stacked, weights, staleness, m, v, *, mode, beta,
                    normalizer, lr=1.0, beta1=0.9, beta2=0.99, eps=1e-3,
                    blk=2048):
    """Fused async-flush aggregation: FedAST staleness discount +
    weighted reduce + server-optimizer moment update in one pass
    (kernels/fedavg.py). On TPU/GPU this is the compiled Pallas kernel;
    on CPU the whole composition runs as ONE jitted jnp program — the
    repo rule that interpret-mode Pallas is a correctness oracle, not a
    fast path. Returns ``(update, new_m, new_v)``, each (N,) f32."""
    if jax.default_backend() == "cpu":
        f32 = jnp.float32
        return _fused_ref_jit(
            jnp.asarray(stacked, f32), jnp.asarray(weights, f32),
            jnp.asarray(staleness, f32), jnp.asarray(m, f32),
            jnp.asarray(v, f32), jnp.asarray(beta, f32),
            jnp.asarray(normalizer, f32), jnp.asarray(lr, f32),
            jnp.asarray(beta1, f32), jnp.asarray(beta2, f32),
            jnp.asarray(eps, f32), mode=mode)
    return fused_aggregate_pallas(
        stacked, weights, staleness, m, v, mode=mode, beta=beta,
        normalizer=normalizer, lr=lr, beta1=beta1, beta2=beta2, eps=eps,
        blk=blk, interpret=False)


def rmsnorm(x, w, *, eps=1e-6):
    """Fused RMSNorm (one HBM read + write per activation tile)."""
    return rmsnorm_pallas(x, w, eps=eps, interpret=not _on_tpu())


def gated_rmsnorm(x, z, w, *, eps=1e-6):
    """Fused rms_norm(x * silu(z)) * w (Mamba2 output gate)."""
    return gated_rmsnorm_pallas(x, z, w, eps=eps, interpret=not _on_tpu())
