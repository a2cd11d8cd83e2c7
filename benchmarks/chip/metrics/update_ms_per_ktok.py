"""Device milliseconds of the client local-update programs (every chip,
both tasks) per 1,000 tokens of the configuration's task."""
UPDATE_PROGRAMS = ("jit_local_fn", "jit_opt_local_fn", "jit_train_step")


def read(ctx):
    ns = sum(d.module_ns(lambda m: m in UPDATE_PROGRAMS) for d in ctx.trace.devices.values())
    if not ns or not ctx.tokens:
        return None
    return ns / 1e6 / (ctx.tokens / 1e3)
