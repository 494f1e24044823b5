"""Host time of the KV store's versioned multi-get per order answered, in
microseconds (``store.lookup_batch_versioned`` span)."""


def read(ctx, metric):
    span = ctx.trace["spans"].get("store.lookup_batch_versioned")
    if not span or not ctx.orders:
        return None
    return span["total_s"] / ctx.orders * 1e6
