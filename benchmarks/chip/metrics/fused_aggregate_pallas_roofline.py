"""Share of its HBM roofline that the fused FedAdam flush kernel reached on
the configuration's task: (K*N deltas + m, v read; update, m, v written)
* 4 B at peak bandwidth over the kernel's device time."""
import counts
from _fold import roofline


def read(ctx):
    return roofline(ctx, "_fused", counts.fedadam_fold_bytes)
