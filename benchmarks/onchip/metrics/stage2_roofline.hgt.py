"""Share of its roofline HGT's fused stage-2 kernel reached, %: the least
time the chip could take for the window's flushes
(``work_counts_hgt.stage2_flush``, per real order) over the device time of
the ``stage2_score`` kernels in the trace."""
import work_counts
import work_counts_hgt


def read(ctx, metric):
    t = sum(v for k, v in ctx.trace["kernel_s"].items() if "stage2_score" in k)
    model = ctx.config["service"]["model"]
    if t <= 0 or not ctx.flush_sizes or model["gnn_type"] != "hgt":
        return None
    least = sum(work_counts.roofline_s(
        *work_counts_hgt.stage2_flush(model, n, ctx.slots_per_order), ctx.peak)
        for n in ctx.flush_sizes)
    return 100.0 * least / t
