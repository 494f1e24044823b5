"""Share of its roofline the fused stage-2 kernel reached, %: the least
time the chip could take for the window's flushes (operations and bytes
of the algorithm, ``work_counts.stage2_flush``, per real order) over the
device time of the ``stage2_score`` kernel in the trace."""
import work_counts


def read(ctx, metric):
    t = sum(v for k, v in ctx.trace["kernel_s"].items() if "stage2_score" in k)
    if t <= 0 or not ctx.flush_sizes:
        return None
    model = ctx.config["service"]["model"]
    least = sum(work_counts.roofline_s(
        *work_counts.stage2_flush(model, n, ctx.slots_per_order), ctx.peak)
        for n in ctx.flush_sizes)
    return 100.0 * least / t
