"""Host time of one snapshot close's refresh, ms: the mean
``refresher.on_windows_closed`` span (community packing, subgraph
materialisation, padding, stage 1, KV puts)."""


def read(ctx, metric):
    span = ctx.trace["spans"].get("refresher.on_windows_closed")
    if not span or not span["count"]:
        return None
    return span["total_s"] / span["count"] * 1e3
