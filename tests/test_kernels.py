"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fedavg import fedavg_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssm_scan import ssd_scan_pallas
from repro.kernels.ref import ref_attention, ref_fedavg, ref_ssd

KEY = jax.random.PRNGKey(0)


def rnd(shape, dtype=jnp.float32, seed=0, scale=1.0):
    return (scale * jax.random.normal(jax.random.PRNGKey(seed), shape)
            ).astype(dtype)


@pytest.mark.parametrize("B,H,KV,S,hd", [
    (1, 2, 2, 128, 64),
    (2, 4, 2, 256, 64),     # GQA 2:1
    (1, 8, 2, 128, 32),     # GQA 4:1
    (2, 3, 1, 192, 16),     # odd head count, MQA
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, KV, S, hd, causal):
    q = rnd((B, H, S, hd), seed=1)
    k = rnd((B, KV, S, hd), seed=2)
    v = rnd((B, KV, S, hd), seed=3)
    out = flash_attention_pallas(q, k, v, causal=causal, blk_q=64, blk_k=64,
                                 interpret=True)
    ref = ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_bf16():
    q = rnd((1, 2, 128, 64), jnp.bfloat16, seed=4)
    k = rnd((1, 2, 128, 64), jnp.bfloat16, seed=5)
    v = rnd((1, 2, 128, 64), jnp.bfloat16, seed=6)
    out = flash_attention_pallas(q, k, v, interpret=True)
    ref = ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


@pytest.mark.parametrize("blocks", [(32, 32), (64, 128), (128, 64)])
def test_flash_attention_block_shape_invariance(blocks):
    bq, bk = blocks
    q = rnd((1, 2, 256, 32), seed=7)
    k = rnd((1, 2, 256, 32), seed=8)
    v = rnd((1, 2, 256, 32), seed=9)
    out = flash_attention_pallas(q, k, v, blk_q=bq, blk_k=bk, interpret=True)
    ref = ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("B,H,L,P,N,chunk", [
    (1, 1, 64, 16, 8, 16),
    (2, 3, 128, 32, 16, 32),
    (1, 2, 96, 8, 4, 48),
    (2, 1, 256, 64, 64, 128),    # mamba2-like dims
])
def test_ssd_scan_sweep(B, H, L, P, N, chunk):
    x = rnd((B, H, L, P), seed=10, scale=0.5)
    a = -jax.nn.softplus(rnd((B, H, L), seed=11))
    b = rnd((B, H, L, N), seed=12, scale=0.3)
    c = rnd((B, H, L, N), seed=13, scale=0.3)
    out = ssd_scan_pallas(x, a, b, c, chunk=chunk, interpret=True)
    ref = ref_ssd(x, a, b, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-4, rtol=1e-3)


def test_ssd_scan_state_continuity():
    """Chunked result must be invariant to the chunk size (state passes
    correctly across chunk boundaries)."""
    x = rnd((1, 2, 128, 16), seed=14, scale=0.5)
    a = -jax.nn.softplus(rnd((1, 2, 128), seed=15))
    b = rnd((1, 2, 128, 8), seed=16, scale=0.3)
    c = rnd((1, 2, 128, 8), seed=17, scale=0.3)
    o1 = ssd_scan_pallas(x, a, b, c, chunk=16, interpret=True)
    o2 = ssd_scan_pallas(x, a, b, c, chunk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=5e-5,
                               rtol=1e-4)


@pytest.mark.parametrize("K,N,blk", [
    (4, 1000, 256), (16, 4096, 2048), (7, 12345, 512),  # non-divisible N
    (3, 2500, 1024), (5, 4160, 2048),    # ragged last block, N % 128 != 0
    (4, 70000, None),                    # the derived tile, ragged
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_sweep(K, N, blk, dtype):
    st = rnd((K, N), dtype, seed=18)
    w = jax.nn.softmax(rnd((K,), seed=19))
    out = fedavg_pallas(st, w.astype(dtype), blk=blk, interpret=True)
    ref = ref_fedavg(st, w.astype(dtype))
    atol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_fedavg_block_fits_vmem_and_moves_a_mebibyte():
    from repro.kernels.fedavg import fedavg_block

    for k in range(1, 17):
        blk = fedavg_block(k)
        assert blk % 128 == 0
        rows = -(-k // 8) * 8 + 8           # (K, blk) in, (1, blk) out
        assert 2 * rows * blk * 4 <= 12 << 20
        # a mebibyte a step, unless one more lane tile would not fit
        assert k * blk * 4 >= 1 << 20 or 2 * rows * (blk + 128) * 4 > 12 << 20
    assert fedavg_block(4) == 64 * 1024


def test_fedavg_matches_server_aggregate():
    """The Pallas kernel computes exactly fed/server.py's aggregate on the
    flattened cohort."""
    from repro.fed.server import aggregate
    K = 5
    cohort = {"w": rnd((K, 8, 4), seed=20), "b": rnd((K, 6), seed=21)}
    weights = jax.nn.softmax(rnd((K,), seed=22))
    expect = aggregate(cohort, weights)
    flat = jnp.concatenate([cohort["w"].reshape(K, -1),
                            cohort["b"].reshape(K, -1)], axis=1)
    got = fedavg_pallas(flat, weights, blk=16, interpret=True)
    exp_flat = jnp.concatenate([expect["w"].ravel(), expect["b"].ravel()])
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp_flat),
                               atol=1e-5)


def test_model_attention_consistent_with_kernel():
    """models/attention.py chunked jnp path == the Pallas kernel (the model
    path is what the dry-run lowers; the kernel is the TPU deployment)."""
    from repro.models.attention import _sdpa_chunked
    B, H, KV, S, hd = 1, 4, 2, 128, 32
    q = rnd((B, S, H, hd), seed=23)
    k = rnd((B, S, KV, hd), seed=24)
    v = rnd((B, S, KV, hd), seed=25)
    pos = jnp.arange(S, dtype=jnp.int32)
    out_model = _sdpa_chunked(q, k, v, pos, pos, hd ** -0.5, causal=True,
                              chunk=64)
    out_kernel = flash_attention_pallas(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_model), np.asarray(out_kernel.transpose(0, 2, 1, 3)),
        atol=2e-5)


@pytest.mark.parametrize("shape", [(4, 64), (2, 3, 128), (130, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel_sweep(shape, dtype):
    from repro.kernels.rmsnorm import rmsnorm_pallas
    from repro.kernels.ref import ref_rmsnorm
    x = rnd(shape, dtype, seed=30)
    w = 1.0 + 0.1 * rnd(shape[-1:], dtype, seed=31)
    out = rmsnorm_pallas(x, w, blk_rows=64, interpret=True)
    ref = ref_rmsnorm(x, w)
    atol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_gated_rmsnorm_matches_model_path():
    """Kernel == models/ssm.py's gated-norm composition."""
    from repro.kernels.rmsnorm import gated_rmsnorm_pallas
    from repro.models.layers import rms_norm
    x = rnd((6, 128), seed=32)
    z = rnd((6, 128), seed=33)
    w = 1.0 + 0.1 * rnd((128,), seed=34)
    out = gated_rmsnorm_pallas(x, z, w, interpret=True)
    ref = rms_norm(x * jax.nn.silu(z), w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_rmsnorm_matches_model_rms_norm():
    from repro.kernels.rmsnorm import rmsnorm_pallas
    from repro.models.layers import rms_norm
    x = rnd((5, 96), seed=35)
    w = rnd((96,), seed=36)
    np.testing.assert_allclose(
        np.asarray(rmsnorm_pallas(x, w, interpret=True)),
        np.asarray(rms_norm(x, w)), atol=2e-5)
