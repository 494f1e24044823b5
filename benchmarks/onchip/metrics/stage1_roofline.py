"""Share of its roofline stage 1 reached, %: the least time the chip could
take for the window's refresh graphs (``work_counts.stage1_graph`` over
real nodes and edges, never the one-hot gather's O(N^2 * D)) over the
device time of the programs that run the stage-1 kernels."""
import work_counts


def read(ctx, metric):
    t = ctx.trace["program_s"].get("stage1", 0.0)
    if t <= 0 or not ctx.stage1_graphs:
        return None
    model = ctx.config["service"]["model"]
    least = sum(work_counts.roofline_s(*work_counts.stage1_graph(model, n, e),
                                       ctx.peak)
                for n, e in ctx.stage1_graphs)
    return 100.0 * least / t
