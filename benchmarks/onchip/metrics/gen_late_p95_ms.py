"""95th percentile of how late the load generator sent its orders, ms
(host clock).  Lateness past the knee grows through the window."""
import numpy as np


def read(ctx, metric):
    if len(ctx.late) == 0:
        return None
    return float(np.percentile(ctx.late, 95) * 1e3)
