#!/usr/bin/env python3
"""Chip smoke test: the MMFL trainer's main path on a TPU, at smollm-135m's
published width (30 layers, d_model 576, 9/3 heads, d_ff 1536, vocab
49152, float32; random weights from a fixed seed).

    python chip_smoke.py               # one chip: sync, sync FedAvg, async
    python chip_smoke.py --four-chips  # sharded FedAvg on 4 chips vs vmap

Every phase trains through ``repro.api.run_scenario``, the entry point a
user calls, with a second architecture (qwen3-0.6b at its tiny preset)
beside smollm-135m so that FedFair allocation has a choice to make. Any
failed check raises, so the script exits non-zero; the last line of
standard output is the JSON result, printed only after every check
passed. The script stops before any phase when JAX finds no TPU, and runs
everything in this one process: a chip belongs to one process.

The sizes below are chosen to fit one v5e (16 GB of HBM) from compiles of
each phase's programs; they are not benchmark settings.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402

from repro.api import (AllocationSpec, ClientBatch, ClientPopulationSpec,  # noqa: E402
                       CohortTask, RuntimeSpec, ScenarioSpec, TaskSpec,
                       get_backend, run_scenario)
from repro.api.aggregator import aggregator_from_config  # noqa: E402
from repro.api.backend import (_cohort_sum, _norm_weights,  # noqa: E402
                               _pallas_aggregate, _sharded_fold, _weighted_sum_jnp)
from repro.configs import get_config, smoke_config  # noqa: E402
from repro.launch.train import assemble_batch, build_task, make_dataset  # noqa: E402
from repro.models import get_api  # noqa: E402

SMOLLM = "smollm-135m"
PARTNER = "qwen3-0.6b"     # always at its tiny preset
N_CLIENTS = 8
SEQ = 256
SYNC_BATCH = 8             # tau=1: one fused adamw step on the mixed batch
FEDAVG_BATCH = 4           # tau=2: every batch row holds its own params copy
ASYNC_SEQ = 128            # a client's batch is its 4 shards of this length
ARRIVALS = 12
BUFFER = 4
# the first smollm loss of a random init sits near ln(vocab)
FIRST_LOSS_NATS = 2.0
# Pallas vs jnp weighted sum of one cohort, as a share of each leaf's
# largest entry: an f32 sum of K rows errs ~K * 6e-8; a product rounded
# to bf16 on the way would err ~2e-3
AGG_RTOL = 1e-5
# sharded (4 chips) vs vmap (1 chip), as a share of the largest change a
# round makes to each leaf: the two differ only in summation order
SHARDED_RTOL = 1e-3
# beyond those shares, a few f32 ulps of each value: a params entry near
# 1.0 that a round moves by ~1e-5 still rounds at ~1.2e-7
F32_SLACK = 1e-6


def smollm_config(preset: str):
    return (get_config if preset == "full" else smoke_config)(SMOLLM)


def task_specs(preset: str, partner: bool = True, **opts):
    tasks = [TaskSpec(SMOLLM, family="arch", options={"preset": preset, **opts})]
    if partner:
        tasks.append(TaskSpec(PARTNER, family="arch", options={"preset": "tiny", **opts}))
    return tasks


def sync_spec(preset, *, tau, batch, backend, rounds=3, partner=True) -> ScenarioSpec:
    return ScenarioSpec(
        name=f"chip-smoke-sync-tau{tau}-{backend}",
        seed=0,
        data_seed=0,
        tasks=task_specs(preset, partner, seq=SEQ, batch=batch, tau=tau),
        clients=ClientPopulationSpec(n_clients=N_CLIENTS, participation=0.5),
        allocation=AllocationSpec(strategy="fedfair"),
        runtime=RuntimeSpec(mode="sync", backend=backend, rounds=rounds, tau=tau))


def async_spec(preset) -> ScenarioSpec:
    return ScenarioSpec(
        name="chip-smoke-async",
        seed=0,
        data_seed=0,
        tasks=task_specs(preset, seq=ASYNC_SEQ, tau=1),
        clients=ClientPopulationSpec(n_clients=N_CLIENTS, speed_profile="bimodal"),
        allocation=AllocationSpec(strategy="fedfair"),
        runtime=RuntimeSpec(mode="async", backend="vmap", total_arrivals=ARRIVALS,
                            buffer_size=BUFFER, aggregator="fedadam"))


def run_phase(label: str, spec: ScenarioSpec):
    t0 = time.perf_counter()
    result = run_scenario(spec, verbose=True)
    wall = time.perf_counter() - t0
    losses = {k: round(v, 4) for k, v in result.final_loss.items()}
    # the process-wide peak so far: it rises at the phase that sets it
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    peak = "not reported" if peak is None else f"{peak / 1e9:.2f} GB"
    print(f"[{label}] wall {wall:.1f} s (compiles included), final losses {losses}, "
          f"device 0 peak memory so far {peak}", flush=True)
    return result


def check_result(label: str, result, preset: str, platform: str) -> None:
    """Every task reports finite losses, smollm's first report sits near
    ln(vocab), and the trained params live on ``platform``."""
    loss = np.asarray(result.loss, np.float64)
    first_report = {}
    for s, name in enumerate(result.task_names):
        # +inf is the coordinator's "not measured yet": a task that drew
        # no clients so far (FedFair's first round favours one task)
        reported = np.flatnonzero(loss[:, s] != np.inf)
        if len(reported) == 0 or not np.isfinite(loss[reported[0]:, s]).all():
            raise AssertionError(f"{label}: {name} losses not all finite: {loss[:, s]}")
        first_report[name] = float(loss[reported[0], s])
    first, ln_v = first_report[SMOLLM], math.log(smollm_config(preset).vocab_size)
    if abs(first - ln_v) > FIRST_LOSS_NATS:
        raise AssertionError(f"{label}: first {SMOLLM} loss {first:.3f} is not within "
                             f"{FIRST_LOSS_NATS} nats of ln(vocab) = {ln_v:.3f}")
    for name, params in zip(result.task_names, result.params):
        found = {d.platform for leaf in jax.tree.leaves(params) for d in leaf.devices()}
        if found != {platform}:
            raise AssertionError(f"{label}: {name} params live on {found}, not {platform}")
    print(f"[{label}] checks passed: first {SMOLLM} loss {first:.4f} "
          f"(ln vocab {ln_v:.4f}), params on {platform}", flush=True)


def phase_sync(preset: str, platform: str) -> None:
    spec = sync_spec(preset, tau=1, batch=SYNC_BATCH, backend="serial")
    check_result("sync", run_phase("sync", spec), preset, platform)


def phase_fedavg(preset: str, platform: str) -> None:
    spec = sync_spec(preset, tau=2, batch=FEDAVG_BATCH, backend="vmap")
    check_result("sync-fedavg", run_phase("sync-fedavg", spec), preset, platform)


def phase_async(preset: str, platform: str) -> None:
    result = run_phase("async", async_spec(preset))
    s = result.task_names.index(SMOLLM)
    if int(result.versions[s]) < 1:
        raise AssertionError(f"async: {SMOLLM} was never aggregated "
                             f"(versions {result.versions.tolist()})")
    check_result("async", result, preset, platform)


def smollm_cohort(preset: str, backend: str):
    """One tau=2 FedAvg cohort of smollm-135m, built as the sync engine
    builds it: the task's own init, a FEDAVG_BATCH-row batch from
    non-uniform client weights, one cohort row per batch row. Returns
    (global params, cohort result, normalised row weights)."""
    task = build_task(SMOLLM, preset, SEQ, FEDAVG_BATCH, tau=2)
    task.pop("opt")
    data = make_dataset(None, task["cfg"], N_CLIENTS, 4, SEQ, seed=0)
    rng = np.random.default_rng(0)
    ids = np.arange(FEDAVG_BATCH)
    batch = assemble_batch(task, data, ids, rng.dirichlet(np.ones(len(ids))), rng)
    rows = {k: v[:, None] for k, v in batch.items() if k != "client_weights"}
    res = get_backend(backend).run_cohort(CohortTask(SMOLLM, task["params"], task["local_fn"]),
                                          ClientBatch(ids, None, (rows,)))
    return task["params"], res, _norm_weights(batch["client_weights"], None)


def worst_rel_err(got, want, origin=None) -> float:
    """Largest |got - want|, less F32_SLACK of |want|, over the largest
    |want - origin|, leaf by leaf (origin 0: over the largest |want|)."""
    worst = 0.0
    origin = jax.tree.map(lambda w: 0.0, want) if origin is None else origin
    for g, w, o in zip(jax.tree.leaves(got), jax.tree.leaves(want), jax.tree.leaves(origin)):
        g, w, o = (np.asarray(a, np.float64) for a in (g, w, o))
        excess = np.maximum(np.abs(g - w) - F32_SLACK * np.abs(w), 0.0).max()
        worst = max(worst, float(excess / max(np.abs(w - o).max(), 1e-30)))
    return worst


def check_fedavg_kernel_matches_jnp(preset: str) -> None:
    """fedavg_pallas against the jnp weighted sum on one real cohort."""
    _, res, norm = smollm_cohort(preset, "vmap")
    got = _pallas_aggregate(res.updates, norm)
    with jax.default_matmul_precision("highest"):
        want = _weighted_sum_jnp(res.updates, norm)
    err = worst_rel_err(got, want)
    print(f"[kernel] fedavg_pallas vs jnp weighted sum on a {len(norm)}-row {SMOLLM} cohort: "
          f"worst relative error {err:.3e} (limit {AGG_RTOL:.0e})", flush=True)
    if not err <= AGG_RTOL:
        raise AssertionError(f"fedavg_pallas disagrees with the jnp weighted sum: {err:.3e}")


def check_kernels_compiled(preset: str) -> None:
    """The aggregates the FedAvg and async phases ran lower to a compiled
    Mosaic kernel (``tpu_custom_call``), not to the interpreter."""
    cfg = smollm_config(preset)
    shapes = jax.eval_shape(lambda k: get_api(cfg).init_params(k, cfg), jax.random.PRNGKey(0))
    K = BUFFER
    stacked = jax.tree.map(lambda a: jax.ShapeDtypeStruct((K,) + a.shape, a.dtype), shapes)
    vec = jax.ShapeDtypeStruct((K,), np.float32)
    fedadam = aggregator_from_config("fedadam", {}, backend=get_backend("vmap"))
    server = jax.eval_shape(fedadam.init, shapes)
    lowered = {
        "fedavg_pallas (vmap aggregate)": jax.jit(
            lambda u, w: get_backend("vmap").aggregate(u, w)).lower(stacked, vec),
        "fused_aggregate_pallas (fedadam flush)": jax.jit(
            lambda u, w, st, sv: fedadam.aggregate_stale(u, w, st, 0.5, sv)).lower(
                stacked, vec, vec, server),
    }
    for name, low in lowered.items():
        if "tpu_custom_call" not in low.as_text():
            raise AssertionError(f"{name} does not lower to a compiled TPU kernel")
        print(f"[kernel] {name}: lowers to tpu_custom_call", flush=True)


def one_chip(preset: str = "full", platform: str = "tpu") -> None:
    phase_sync(preset, platform)
    phase_fedavg(preset, platform)
    phase_async(preset, platform)
    check_kernels_compiled(preset)
    check_fedavg_kernel_matches_jnp(preset)


def four_chips(preset: str = "full", platform: str = "tpu") -> None:
    """One tau=2 FedAvg round of smollm-135m on ``sharded`` over every
    device, against the same round on ``vmap`` on device 0: the cohort's
    updates, the aggregate and the engine's round must agree."""
    from repro.launch.mesh import make_cohort_mesh

    results = {}
    for backend in ("sharded", "vmap"):
        spec = sync_spec(preset, tau=2, batch=FEDAVG_BATCH, backend=backend, rounds=1,
                         partner=False)
        results[backend] = run_phase(f"four-chips {backend}", spec)
        check_result(f"four-chips {backend}", results[backend], preset, platform)
    sharded, vmap = results["sharded"], results["vmap"]
    n_dev = {d for leaf in jax.tree.leaves(sharded.params[0]) for d in leaf.devices()}
    print(f"[four-chips] sharded params span {len(n_dev)} devices", flush=True)
    np.testing.assert_allclose(sharded.loss, vmap.loss, rtol=1e-5)

    p0, res_s, norm = smollm_cohort(preset, "sharded")
    _, res_v, _ = smollm_cohort(preset, "vmap")
    upd_err = worst_rel_err(res_s.updates, res_v.updates,
                            jax.tree.map(lambda p: p[None], p0))
    agg_s = get_backend("sharded").aggregate(res_s.updates, norm)
    agg_v = get_backend("vmap").aggregate(res_v.updates, norm)
    agg_err = worst_rel_err(agg_s, agg_v, p0)
    round_err = worst_rel_err(sharded.params[0], vmap.params[0], p0)
    hlo = _sharded_fold(make_cohort_mesh(), _cohort_sum()).lower(
        res_s.updates, norm).compile().as_text()
    collectives = sorted({c for c in ("all-gather", "all-reduce", "all-to-all",
                                      "collective-permute") if c in hlo})
    print(f"[four-chips] sharded vs vmap, as a share of the round's largest change: "
          f"updates {upd_err:.3e}, aggregate {agg_err:.3e}, engine round {round_err:.3e} "
          f"(limit {SHARDED_RTOL:.0e}); sharded fold collectives {collectives}", flush=True)
    for what, err in (("updates", upd_err), ("aggregate", agg_err), ("round", round_err)):
        if not err <= SHARDED_RTOL:
            raise AssertionError(f"sharded and vmap {what} differ by {err:.3e}")
    if "all-gather" in collectives:
        raise AssertionError("the sharded fold gathers the cohort onto every device")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-vmap FedAvg round on four chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (devices: {devices})")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        sys.exit(f"chip_smoke: needs {need} TPU chips, JAX found {len(devices)}")
    print(f"jax {jax.__version__}; devices {devices}; compilation cache {cache_dir}",
          flush=True)
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)()
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s", flush=True)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform, "kind": d.device_kind,
                                             "count": len(devices)}}))


if __name__ == "__main__":
    main()
