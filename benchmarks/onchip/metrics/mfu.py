"""Served step's share of the chip's bf16 peak, %: stage-2 operations of
the window's flushes over the host wall time of the flushes' spans (KV
gather and stage 2 with its host tail)."""
import work_counts


def read(ctx, metric):
    spans = ctx.trace["spans"]
    wall = sum(spans[k]["total_s"] for k in
               ("store.lookup_batch_versioned", "stage2") if k in spans)
    if wall <= 0 or not ctx.flush_sizes:
        return None
    model = ctx.config["service"]["model"]
    ops = sum(work_counts.stage2_flush(model, n, ctx.slots_per_order)[0]
              for n in ctx.flush_sizes)
    return 100.0 * ops / wall / ctx.peak["bf16_flops_per_s"]
