"""Share of its roofline that the routed experts' grouped matmuls reached:
the least time of each call, max(FLOPs at the bf16 peak, bytes at peak
HBM bandwidth) with the routed rows at their expectation (the family's
``gmm_counts``), summed over the calls, over their device time."""
from _experts import gmm_calls


def read(ctx):
    calls = gmm_calls(ctx)
    if not calls:
        return None
    flops_s = sum(f for f, _, _ in calls) / ctx.peaks["bf16_flops_per_s"]
    bytes_s = sum(b for _, b, _ in calls) / ctx.peaks["hbm_bytes_per_s"]
    least = sum(max(f / ctx.peaks["bf16_flops_per_s"], b / ctx.peaks["hbm_bytes_per_s"])
                for f, b, _ in calls)
    ns = sum(t for _, _, t in calls)
    ctx.log(f"expert grouped matmuls: {len(calls)} calls, {ns / 1e6:.3f} ms on the device; "
            f"least {least * 1e3:.3f} ms (FLOPs alone {flops_s * 1e3:.3f} ms, bytes alone "
            f"{bytes_s * 1e3:.3f} ms)")
    return 100.0 * least / (ns / 1e9)
