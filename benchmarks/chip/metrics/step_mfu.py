"""Model FLOPs of the configuration's tokens in the traced window (forward
and backward, no recompute; counts.py) over the traced seconds, the
chips and the chip's bf16 peak."""
import counts


def read(ctx):
    if not ctx.tokens:
        return None
    flops = ctx.tokens * counts.train_flops_per_token(ctx.cfg, ctx.tr["seq"])
    return 100.0 * flops / (ctx.window_s * ctx.chips * ctx.peaks["bf16_flops_per_s"])
