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
import contextlib
import os
import re

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


@contextlib.contextmanager
def _as_on_tpu():
    """Trace as the chip would: code that picks the Pallas interpreter from
    ``jax.default_backend()`` sees the CPU here and would take it."""
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        yield
    finally:
        jax.default_backend = real


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


def test_fedavg_pallas_default_block_compiles_at_smollm_width(one_chip, n_params):
    from repro.kernels.fedavg import _fedavg_jit

    _assert_kernel(_fedavg_jit.lower(
        _sds((K, n_params), one_chip), _sds((K,), one_chip),
        blk=None, interpret=False).compile())


def test_sync_fold_is_one_program_at_smollm_width(one_chip, smollm, n_params):
    """The vmap backend's fold of a K-row smollm-135m cohort: the ravel, one
    Pallas kernel on the flat (K, N) cohort and the unravel in one compiled
    program, with no pad of the cohort before the kernel."""
    from repro.api.backend import _pallas_aggregate

    cohort = jax.tree.map(lambda a: _sds((K,) + a.shape, one_chip, a.dtype), smollm[2])
    with _as_on_tpu():
        lowered = _pallas_aggregate.lower(cohort, _sds((K,), one_chip))
    text = lowered.compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1
    # the name and flat operand the benchmark's fold roofline looks for
    assert calls[0].lstrip().startswith("%_fedavg") and f"f32[{K},{n_params}]" in calls[0]
    assert not re.search(rf"f32\[{K},\d+\]\S* pad\(", text)


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
    partial sums of the compiled fold, nested in the shard_map, and one
    all-reduce, never a gather of the cohort."""
    from repro.api.backend import _pallas_aggregate, _sharded_fold

    mesh = Mesh(np.array(topo.devices[:4]), ("clients",))
    clients = NamedSharding(mesh, PartitionSpec("clients"))
    cohort = {"embed": _sds((K, 49152, 576), clients),
              "w": _sds((K, 30, 576, 1536), clients)}
    with _as_on_tpu():
        lowered = _sharded_fold(mesh, _pallas_aggregate).lower(cohort, _sds((K,), clients))
    text = lowered.compile().as_text()
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


# ------------------------------------------------ DeepSeek-V2-Lite's cell
#
# The benchmark's deepseek-v2-lite cell: one chip's share of the model
# (benchmarks/chip/configs/deepseek-v2-lite.json, through its family's
# ModelConfig), 2 cohort rows x seq 2048, tau 2, vmap backend.

DS_ROWS, DS_SEQ, DS_TAU = 2, 2048, 2


def _bench_model(name):
    """A benchmark configuration (benchmarks/chip/configs/<name>.json)
    through its family's ModelConfig: (config, api, parameter shapes)."""
    import json
    import sys
    from pathlib import Path

    chip = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    if str(chip) not in sys.path:
        sys.path.insert(0, str(chip))
    import reference

    cfg = json.loads((chip / "configs" / f"{name}.json").read_text())
    mc = reference.family(cfg).model_config(cfg, name)
    api = get_api(mc)
    shapes = jax.eval_shape(lambda k: api.init_params(k, mc), jax.random.PRNGKey(0))
    return mc, api, shapes


@pytest.fixture(scope="module")
def deepseek():
    return _bench_model("deepseek-v2-lite")


def test_deepseek_local_update_fits_v5e_hbm_with_the_grouped_kernel(one_chip, deepseek):
    """The vmapped local update (the backend's jit of arch_local_fn) at the
    cell's rows and seq: under one chip's HBM, with the routed experts'
    grouped matmuls (forward, and the backward's input and weight
    gradients) as the TPU's grouped kernel."""
    from repro.launch.train import arch_local_fn

    mc, api, shapes = deepseek
    params = jax.tree.map(lambda a: _sds(a.shape, one_chip, a.dtype), shapes)
    toks = _sds((DS_ROWS, 1, DS_SEQ), one_chip, jnp.int32)
    step = jax.jit(jax.vmap(arch_local_fn(api, mc, DS_TAU, 5e-3), in_axes=(None, 0, 0)))
    with _as_on_tpu():
        compiled = step.lower(params, None, {"tokens": toks, "labels": toks}).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, used
    # 4 sparse layers x 3 projections x (forward, input and weight gradient)
    # lie in one scanned layer: 3 forward, 3 recomputed and 6 backward
    kernels = re.findall(r"%ragged-dot-none[\w.]* = \S+ custom-call\(", compiled.as_text())
    assert len(kernels) == 12


def test_deepseek_fold_fits_v5e_hbm_beside_the_params(one_chip, deepseek):
    """The one-program Pallas fold of the cell's 2-row cohort, with the
    global params the engine keeps resident, under one chip's HBM."""
    from repro.api.backend import _pallas_aggregate

    _, _, shapes = deepseek
    n = int(sum(np.prod(a.shape) for a in jax.tree.leaves(shapes)))
    assert n == 535_060_992
    cohort = jax.tree.map(lambda a: _sds((DS_ROWS,) + a.shape, one_chip, a.dtype), shapes)
    with _as_on_tpu():
        compiled = _pallas_aggregate.lower(cohort, _sds((DS_ROWS,), one_chip)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used + 4 * n < V5E_HBM_BYTES, used
    assert f"f32[{DS_ROWS},{n}]" in compiled.as_text()


# ------------------------------------------------------ qwen3-0.6b's cell
#
# The benchmark's qwen3-0.6b cell: tau 1, the fused AdamW step as a
# one-row cohort on the serial backend, its state handed back by the
# engine's take-row.


def test_fused_take_row_writes_no_second_state_copy(one_chip):
    """The take-row of the cell's one-row (params, Adam state) cohort, 16
    layers at published width in float32: the donated row's buffers come
    back as the state, so what it writes beside them (outputs not reused
    from the donation, and temporaries) is under 1 % of the state."""
    from repro.api.backend import _take_row_jit
    from repro.launch.train import server_opt

    _, _, shapes = _bench_model("qwen3-0.6b")
    n = int(sum(np.prod(a.shape) for a in jax.tree.leaves(shapes)))
    assert n == 407_409_664
    state = (shapes, jax.eval_shape(server_opt().init, shapes))
    row = jax.tree.map(lambda a: _sds((1,) + a.shape, one_chip, a.dtype), state)
    state_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(row))
    assert 3 * 4 * n <= state_bytes < 3 * 4 * n + 64
    mem = _take_row_jit.lower(row).compile().memory_analysis()
    fresh = mem.output_size_in_bytes - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert fresh < 0.01 * state_bytes, (fresh, mem)
