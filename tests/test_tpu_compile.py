"""Compile the main path's Pallas kernels, and chip_smoke's sync step, for
a described TPU v5e: no chip is needed, and what the chip's compiler would
refuse (tiling, fast-memory limits, HBM size, kernels it cannot partition)
fails here.

Every array is a shape: smollm-135m's real flat parameter count comes from
``jax.eval_shape`` of its init. The topology is described inside a
module-scoped fixture, never while a module is imported: only one process
at a time may load the TPU library, and the test workers import every test
file. Keep all such compiles in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs import get_config
from repro.models import get_api

V5E_HBM_BYTES = 16 * 2**30
K = 4                      # chip_smoke's FedAvg batch and async buffer
SEQ, BATCH = 256, 8        # chip_smoke's sync phase
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def smollm():
    cfg = get_config("smollm-135m")
    api = get_api(cfg)
    shapes = jax.eval_shape(lambda k: api.init_params(k, cfg), jax.random.PRNGKey(0))
    return cfg, api, shapes


@pytest.fixture(scope="module")
def n_params(smollm):
    return int(sum(np.prod(a.shape) for a in jax.tree.leaves(smollm[2])))


def _sds(shape, sharding, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_smollm_flat_size_is_published(n_params):
    # the tied-embedding 135M config: every compile below is at this width
    assert n_params == 134_515_008


def test_fedavg_pallas_compiles_at_smollm_width(one_chip, n_params):
    from repro.kernels.fedavg import DEFAULT_BLOCK, _fedavg_jit

    _assert_kernel(_fedavg_jit.lower(
        _sds((K, n_params), one_chip), _sds((K,), one_chip),
        blk=DEFAULT_BLOCK, interpret=False).compile())


def test_fused_fedadam_compiles_at_smollm_width(one_chip, n_params):
    from repro.kernels.fedavg import _N_SCALARS, DEFAULT_BLOCK, _fused_jit

    vec, row = _sds((K,), one_chip), _sds((n_params,), one_chip)
    _assert_kernel(_fused_jit.lower(
        _sds((K, n_params), one_chip), vec, vec, _sds((_N_SCALARS,), one_chip),
        row, row, mode="fedadam", blk=DEFAULT_BLOCK, interpret=False).compile())


def test_rmsnorm_compiles_at_smollm_width(one_chip, smollm):
    from repro.kernels.rmsnorm import rmsnorm_pallas

    d = smollm[0].d_model
    _assert_kernel(rmsnorm_pallas.lower(
        _sds((BATCH * 2048, d), one_chip), _sds((d,), one_chip),
        interpret=False).compile())


def test_flash_attention_forward_compiles_at_smollm_width(one_chip, smollm):
    from repro.kernels.flash_attention import flash_attention_pallas

    cfg = smollm[0]
    q = _sds((BATCH, cfg.n_heads, SEQ, cfg.hd), one_chip)
    kv = _sds((BATCH, cfg.n_kv_heads, SEQ, cfg.hd), one_chip)
    _assert_kernel(flash_attention_pallas.lower(q, kv, kv, interpret=False).compile())


def test_sharded_fold_all_reduces_without_gathering(topo):
    """The sharded backend's aggregate on a 4-chip mesh: per-chip kernel
    partial sums and one all-reduce, never a gather of the cohort."""
    from jax.flatten_util import ravel_pytree

    from repro.api.backend import _sharded_fold
    from repro.kernels.fedavg import fedavg_pallas

    mesh = Mesh(np.array(topo.devices[:4]), ("clients",))
    clients = NamedSharding(mesh, PartitionSpec("clients"))

    def local(x, w):
        flat = jax.vmap(lambda p: ravel_pytree(p)[0])(x)
        _, unravel = ravel_pytree(jax.tree.map(lambda leaf: leaf[0], x))
        return unravel(fedavg_pallas(flat, w, interpret=False))

    cohort = {"embed": _sds((K, 49152, 576), clients),
              "w": _sds((K, 30, 576, 1536), clients)}
    text = _sharded_fold(mesh, local).lower(cohort, _sds((K,), clients)).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text and "all-gather" not in text


def test_sync_step_fits_v5e_hbm(one_chip, smollm):
    """chip_smoke's sync phase: the fused adamw step of full-width
    smollm-135m at batch 8, seq 256, with its arguments, outputs and
    temporaries under one chip's HBM and room for a second copy of the
    params and optimizer state that the process keeps resident."""
    from repro.launch.train import arch_fused_step, server_opt

    cfg, api, shapes = smollm
    put = lambda tree: jax.tree.map(lambda a: _sds(a.shape, one_chip, a.dtype), tree)  # noqa: E731
    params = put(shapes)
    opt = put(jax.eval_shape(server_opt().init, shapes))
    toks = _sds((BATCH, SEQ), one_chip, jnp.int32)
    batch = {"tokens": toks, "labels": toks, "client_weights": _sds((BATCH,), one_chip)}
    step, _ = arch_fused_step(api, cfg)
    mem = step.lower(params, opt, batch).compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used + mem.argument_size_in_bytes < V5E_HBM_BYTES, used
