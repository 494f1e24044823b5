"""Whole refresh's share of the chip's bf16 peak, %: stage-1 operations of
the window's refresh graphs (real nodes and edges, ``work_counts``) over
the host wall time of the ``refresher.on_windows_closed`` spans."""
import work_counts


def read(ctx, metric):
    span = ctx.trace["spans"].get("refresher.on_windows_closed")
    if not span or span["total_s"] <= 0 or not ctx.stage1_graphs:
        return None
    model = ctx.config["service"]["model"]
    ops = sum(work_counts.stage1_graph(model, n, e)[0]
              for n, e in ctx.stage1_graphs)
    return 100.0 * ops / span["total_s"] / ctx.peak["bf16_flops_per_s"]
