"""Shared by the expert readers: the routed experts' grouped-matmul calls
(``kernels/gmm.py``: ``jax.lax.ragged_dot``, which a TPU's compiler lowers
to the ``ragged-dot-none`` kernel, forward and backward) inside the client
local-update programs, on every chip, with the shapes the family's counts
read. The evaluation programs' forward calls are left out."""
from __future__ import annotations

import re

import devtrace as trace
import reference
from update_ms_per_ktok import UPDATE_PROGRAMS

GMM = "ragged-dot-none"
_SHAPE = re.compile(r"(?:f32|bf16)\[(\d+(?:,\d+)*)\]")


def _dims(text: str) -> list:
    return [tuple(int(n) for n in m.split(",")) for m in _SHAPE.findall(text)]


def gmm_calls(ctx) -> list:
    """(FLOPs, bytes, device ns) of every local-update grouped-matmul call: the
    family's ``gmm_counts`` from the op's shapes. A call's result is
    (rows, b) from rows (rows, a) and experts (groups, a, b), or, for a
    weight gradient, (groups, a, b) from rows (rows, a) and (rows, b)."""
    counts = getattr(reference.family(ctx.cfg), "gmm_counts", None)
    if counts is None:
        return []
    out = []
    for dev in ctx.trace.devices.values():
        for hlo, s, e in dev.ops_in(lambda m: m in UPDATE_PROGRAMS):
            if trace.op_kind(hlo) != "custom-call" or not trace.op_name(hlo).startswith(GMM):
                continue
            result, operands = hlo.split(" custom-call(", 1)
            res = _dims(result)
            two = [d for d in _dims(operands) if len(d) == 2]
            three = [d for d in _dims(operands) if len(d) == 3]
            if len(res) != 1 or not two:
                continue
            if len(res[0]) == 3:
                (groups, a, b), rows = res[0], two[0][0]
            elif three:
                (rows, a), (groups, _, b) = two[0], three[0]
            else:
                continue
            flops, nbytes = counts(ctx.cfg, rows, groups, a, b)
            out.append((flops, nbytes, e - s))
    return out
