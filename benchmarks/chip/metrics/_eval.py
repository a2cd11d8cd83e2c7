"""Device milliseconds of the evaluation programs per round or flush."""
EVAL_PROGRAMS = ("jit_eval_loss", "jit_eval_acc")


def eval_ms(ctx):
    ns = sum(d.module_ns(lambda m: m in EVAL_PROGRAMS) for d in ctx.trace.devices.values())
    if not ns or not ctx.steps:
        return None
    return ns / 1e6 / ctx.steps
