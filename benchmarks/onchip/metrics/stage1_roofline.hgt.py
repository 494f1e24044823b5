"""Share of its roofline HGT's stage 1 reached, %: the least time the chip
could take for the window's refresh graphs (``work_counts_hgt.stage1_graph``
over real nodes and edges) over the device time of the programs that run
the stage-1 kernels (``hgt_edge_softmax_pallas``)."""
import work_counts
import work_counts_hgt


def read(ctx, metric):
    t = ctx.trace["program_s"].get("stage1", 0.0)
    model = dict(ctx.config["service"]["model"],
                 max_history=ctx.config["service"]["engine"]["max_history"])
    if t <= 0 or not ctx.stage1_graphs or model["gnn_type"] != "hgt":
        return None
    least = sum(work_counts.roofline_s(
        *work_counts_hgt.stage1_graph(model, n, e), ctx.peak)
        for n, e in ctx.stage1_graphs)
    return 100.0 * least / t
