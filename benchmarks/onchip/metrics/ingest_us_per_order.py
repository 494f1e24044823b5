"""Host time of ``engine.ingest`` (DDS growth, partitioner, speed-layer
keys) per order answered, in microseconds: the span's self time, so a
refresh fired from inside it is not counted here."""


def read(ctx, metric):
    span = ctx.trace["spans"].get("engine.ingest")
    if not span or not ctx.orders:
        return None
    return span["self_s"] / ctx.orders * 1e6
