"""HGT refresh's share of the chip's bf16 peak, %: stage-1 operations of
the window's refresh graphs (``work_counts_hgt``, real nodes and edges)
over the host wall time of the ``refresher.on_windows_closed`` spans."""
import work_counts_hgt


def read(ctx, metric):
    span = ctx.trace["spans"].get("refresher.on_windows_closed")
    model = dict(ctx.config["service"]["model"],
                 max_history=ctx.config["service"]["engine"]["max_history"])
    if (not span or span["total_s"] <= 0 or not ctx.stage1_graphs
            or model["gnn_type"] != "hgt"):
        return None
    ops = sum(work_counts_hgt.stage1_graph(model, n, e)[0]
              for n, e in ctx.stage1_graphs)
    return 100.0 * ops / span["total_s"] / ctx.peak["bf16_flops_per_s"]
