"""The readers this repo's MoE and four-chip cells add, on traces built by
hand: the expert readers count the grouped-matmul calls of the client
local-update programs and not those of evaluation, and the all-reduce
reader counts the exchange of the configuration task's fold and not the
partner's. Times are in milliseconds, written as ns."""
import json
import types

import pytest

import counts
import devtrace
import reference
import run
from conftest import HERE

MS = 1_000_000
PEAKS = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]


def config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def ctx_of(cfg, devices, chips=1, steps=2, tokens=4096):
    trace = devtrace.Trace({f"/device:TPU:{i}": d for i, d in enumerate(devices)}, [])
    return types.SimpleNamespace(trace=trace, cfg=cfg, chips=chips, steps=steps, tokens=tokens,
                                 peaks=PEAKS, log=lambda *_: None)


GMM = ("%ragged-dot-none.{i} = f32[4096,1408]{{1,0}} custom-call(f32[4096,2048]{{1,0}} %x, "
       "f32[8,2048,1408]{{2,1,0}} %w, s32[8]{{0}} %g), custom_call_target=\"ragged\"")


def test_expert_readers_count_the_local_update_and_not_evaluation():
    cfg = config("deepseek-v2-lite")
    dev = devtrace.Device(
        ops=[(GMM.format(i=1), 10 * MS, 20 * MS), (GMM.format(i=2), 30 * MS, 36 * MS),
             (GMM.format(i=3), 150 * MS, 190 * MS)],
        modules=[("jit_local_fn", 0, 100 * MS), ("jit_eval_loss", 100 * MS, 200 * MS)])
    ctx = ctx_of(cfg, [dev])
    assert run.reader("expert_ms_per_ktok")(ctx) == pytest.approx(16 / 4.096)
    flops, nbytes = reference.family(cfg).gmm_counts(cfg, 4096, 8, 2048, 1408)
    least = max(flops / PEAKS["bf16_flops_per_s"], nbytes / PEAKS["hbm_bytes_per_s"])
    assert run.reader("expert_gmm_roofline")(ctx) == pytest.approx(
        100 * (least * 2) / 16e-3)
    evaluation_only = devtrace.Device(ops=dev.ops[2:], modules=dev.modules)
    assert run.reader("expert_ms_per_ktok")(ctx_of(cfg, [evaluation_only])) is None


def _fold_device(n, partner_n, task_ms):
    """The task's fold program at 0-50 ms (kernel, then its all-reduce of
    ``task_ms``), the partner's at 60-80 ms, and an all-reduce outside both."""
    kernel = "%_fedavg.{i} = f32[{n}]{{0}} custom-call(f32[4,{n}]{{1,0}} %c, f32[4]{{0}} %w)"
    reduce_ = "%all-reduce.{i} = f32[{n}]{{0}} all-reduce(f32[{n}]{{0}} %s), to_apply=%add"
    return devtrace.Device(
        ops=[(kernel.format(i=1, n=n), 5 * MS, 25 * MS),
             (reduce_.format(i=1, n=n), 30 * MS, (30 + task_ms) * MS),
             (kernel.format(i=2, n=partner_n), 62 * MS, 64 * MS),
             (reduce_.format(i=2, n=partner_n), 66 * MS, 70 * MS),
             (reduce_.format(i=3, n=n), 90 * MS, 99 * MS)],
        modules=[("jit__lambda_", 0, 50 * MS), ("jit__lambda_", 60 * MS, 80 * MS)])


def test_allreduce_counts_the_tasks_fold_on_the_slowest_chip():
    cfg = config("smollm-135m")
    n = counts.trained_params(cfg, reference.padded_vocab(cfg["vocab_size"]))
    devices = [_fold_device(n, 4096, ms) for ms in (10, 12, 8, 11)]
    assert run.reader("allreduce_ms.round")(ctx_of(cfg, devices, chips=4)) == pytest.approx(12 / 2)
    assert run.reader("allreduce_ms.round")(ctx_of(cfg, devices[:1], chips=1)) is None
