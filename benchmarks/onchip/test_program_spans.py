"""The program's own spans (``repro.utils.spans``) leave the benchmark's
accepted trace reduction as it is.

A traced run may hold program spans nested inside the harness's wrappers
(``layer_spans``).  The accepted per-layer metrics read span counts, totals
and self times, idle gaps and device time computed over the harness's
names; these tests check that they read the same with or without program
spans in the trace.  Runs on the CPU.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import layer_spans  # noqa: E402
import trace_metrics  # noqa: E402

from repro.utils import spans  # noqa: E402

MS = 1_000_000
HARNESS = layer_spans.SPAN_NAMES + (trace_metrics.WINDOW_SPAN,)


def _planes(with_program: bool):
    """One window: a backlog-like order (facade, ingest with its three
    steps, a flush with the KV gather and the stage-2 call split in three)
    and a refresh with its four children, over two stage-2 kernels."""
    def ev(name, s, e):
        return SimpleNamespace(name=name, start_ns=s * MS,
                               duration_ns=(e - s) * MS)

    host = [ev("bench.window", 0, 100), ev("pool.submit", 20, 60),
            ev("engine.ingest", 5, 19), ev("store.lookup_batch_versioned", 22, 30),
            ev("stage2", 31, 55), ev("refresher.on_windows_closed", 62, 95),
            ev("gc.gen2", 96, 98)]
    program = [ev("service.submit", 2, 61), ev("ingest.order", 6, 18),
               ev("ingest.keys", 6, 9), ev("ingest.dds", 9, 14),
               ev("ingest.partition", 14, 17), ev("batch.flush", 21, 59),
               ev("batch.assemble", 21, 22), ev("kv.lookup", 22, 30),
               ev("s2.launch", 31, 40), ev("s2.sync", 40, 53),
               ev("s2.tail", 53, 55), ev("batch.results", 56, 58),
               ev("refresh", 62, 94), ev("refresh.snapshot", 62, 72),
               ev("refresh.pad", 72, 80), ev("refresh.stage1", 80, 90),
               ev("refresh.put", 90, 93)]
    events = host + (program if with_program else [])
    return [
        SimpleNamespace(name="/device:TPU:0", lines=[
            SimpleNamespace(name="XLA Ops", events=[
                ev("%stage2_score_pallas.1 = f32[16]", 45, 46),
                ev("%edge_softmax_agg_pallas = f32[64]", 82, 88)]),
            SimpleNamespace(name="XLA Modules", events=[
                ev("jit__lambda", 44, 47), ev("jit__lambda", 81, 89)])]),
        SimpleNamespace(name="/host:CPU", lines=[
            SimpleNamespace(name="python3", events=events)]),
    ]


def test_program_spans_leave_the_accepted_reduction_unchanged():
    bare = trace_metrics.reduce(trace_metrics.from_planes(_planes(False),
                                                          HARNESS))
    nested = trace_metrics.reduce(trace_metrics.from_planes(_planes(True),
                                                            HARNESS))
    assert nested == bare
    # the harness's self times stay what the harness alone defines: the
    # program spans nested in ``stage2`` do not eat into it
    assert nested["spans"]["stage2"]["self_s"] == pytest.approx(0.024)
    assert nested["spans"]["pool.submit"]["self_s"] == pytest.approx(
        0.040 - 0.008 - 0.024)
    assert not set(nested["spans"]) & set(spans.SPANS)
    assert not {k for k, _ in nested["idle_gaps"]} & set(spans.SPANS)


def test_a_recorded_trace_with_program_spans_reduces_as_without(tmp_path):
    """Program spans recorded by the profiler inside a harness span are
    invisible to the accepted reduction: ``stage2`` keeps its whole time as
    self time."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    spans.enable(True)
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        with TraceAnnotation("bench.window"):
            for i in range(3):
                with TraceAnnotation("stage2"):
                    with spans.span("batch.flush", seq=i, n=16,
                                    trigger="size"):
                        with spans.span("s2.launch"):
                            out = f(x)
                        with spans.span("s2.sync"):
                            out.block_until_ready()
                            time.sleep(0.001)
        jax.profiler.stop_trace()
    finally:
        spans.enable(False)
    r = trace_metrics.reduce(trace_metrics.load(str(tmp_path), HARNESS))
    st = r["spans"]["stage2"]
    assert st["count"] == 3
    assert st["self_s"] == pytest.approx(st["total_s"])
    assert set(r["spans"]) <= set(layer_spans.SPAN_NAMES)
    assert sum(dict(r["idle_gaps"]).values()) + r["busy_s"] == \
        pytest.approx(r["window_s"])
