"""Share of its HBM roofline that the Pallas fedavg kernel reached on the
configuration's task: (K*N read + N written) * 4 B at peak bandwidth over
the kernel's device time. The algorithm's bytes, not the padded copy."""
import counts
from _fold import roofline


def read(ctx):
    return roofline(ctx, "_fedavg", counts.fedavg_fold_bytes)
