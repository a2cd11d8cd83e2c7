"""Device milliseconds of all-reduce operations per sync round on the
chip that spent most in them: the sharded fold's one exchange a round,
counted inside the programs that run the Pallas fedavg kernel on the
configuration's flat update (its task's fold, not the partner's)."""
import counts
import devtrace as trace
import reference
from _fold import _SHAPE, BLOCK


def _task_folds(dev, n: int) -> list:
    """(start, end) of each program on ``dev`` that ran the fedavg kernel
    on a cohort operand of the task's ``n`` parameters."""
    kernels = []
    for hlo, s, _ in dev.ops:
        if trace.op_kind(hlo) != "custom-call" or not trace.op_name(hlo).startswith("_fedavg"):
            continue
        operands = hlo.split("custom-call(", 1)[-1]
        if any(0 <= int(m) - n < BLOCK for _, m in _SHAPE.findall(operands)):
            kernels.append(s)
    return [(s, e) for _, s, e in dev.modules if any(s <= k < e for k in kernels)]


def read(ctx):
    if ctx.chips < 2 or not ctx.steps:
        return None
    n = counts.trained_params(ctx.cfg, reference.padded_vocab(ctx.cfg["vocab_size"]))
    per_chip = []
    for d in ctx.trace.devices.values():
        folds = _task_folds(d, n)
        per_chip.append(sum(e - s for hlo, s, e in d.ops
                            if trace.op_kind(hlo).startswith("all-reduce")
                            and any(a <= s < b for a, b in folds)))
    if not max(per_chip):
        return None
    ctx.log(f"all-reduce device ms per chip in the task's fold over the trace: "
            f"{[round(ns / 1e6, 3) for ns in per_chip]}; {ctx.steps} rounds")
    return max(per_chip) / 1e6 / ctx.steps
