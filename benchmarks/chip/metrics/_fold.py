"""Shared by the fold roofline readers: the Pallas fold kernel's calls on
the configuration's task, and the least time each could take."""
from __future__ import annotations

import re

import counts
import reference
import devtrace as trace

_SHAPE = re.compile(r"f32\[(\d+),(\d+)\]")
BLOCK = 2048          # a kernel's parameter axis is padded by less than one block


def kernel_calls(ctx, name_prefix: str):
    """(K, trace op) for every custom-call op named ``name_prefix...`` whose
    cohort operand is the configuration's flat update, on every chip."""
    n = counts.trained_params(ctx.cfg, reference.padded_vocab(ctx.cfg["vocab_size"]))
    out = []
    for dev in ctx.trace.devices.values():
        for hlo, s, e in dev.ops:
            if trace.op_kind(hlo) != "custom-call" or not trace.op_name(hlo).startswith(name_prefix):
                continue
            operands = hlo.split("custom-call(", 1)[-1]
            shapes = [(int(k), int(m)) for k, m in _SHAPE.findall(operands)]
            cohort = [(k, m) for k, m in shapes if k > 1 and 0 <= m - n < BLOCK]
            if cohort:
                out.append((cohort[0][0], e - s))
    return n, out


def roofline(ctx, name_prefix: str, fold_bytes) -> float | None:
    n, calls = kernel_calls(ctx, name_prefix)
    if not calls:
        return None
    least = sum(fold_bytes(k, n) for k, _ in calls) / ctx.peaks["hbm_bytes_per_s"]
    ctx.log(f"{name_prefix}: {len(calls)} calls on the configuration's task, kernel "
            f"{sum(d for _, d in calls) / 1e6:.3f} ms, least {least * 1e3:.3f} ms (HBM-bound)")
    return 100.0 * least / (sum(d for _, d in calls) / 1e9)
