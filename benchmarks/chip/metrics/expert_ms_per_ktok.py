"""Device milliseconds of the routed experts' grouped matmuls (forward and
backward, every chip) per 1,000 tokens of the configuration's task."""
from _experts import gmm_calls


def read(ctx):
    calls = gmm_calls(ctx)
    if not calls or not ctx.tokens:
        return None
    return sum(ns for _, _, ns in calls) / 1e6 / (ctx.tokens / 1e3)
