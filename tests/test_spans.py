"""`repro.utils.spans`: the program's own profiler spans and compile counter."""
import glob

import jax
import numpy as np
import pytest

from repro.core import LNNConfig, lnn_init
from repro.data import SynthConfig, generate_event_stream
from repro.service import FraudService, ModelSection, ServiceConfig
from repro.utils import spans


@pytest.fixture
def spans_on():
    spans.enable(True)
    try:
        yield
    finally:
        spans.enable(False)


@pytest.fixture(scope="module")
def world():
    events, g, _ = generate_event_stream(
        SynthConfig(num_users=30, num_rings=1, seed=3), rate_per_s=500.0)
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=16,
                    feat_dim=g.order_features.shape[1])
    params = lnn_init(jax.random.PRNGKey(0), cfg)
    sc = ServiceConfig(mode="streaming",
                       model=ModelSection.from_lnn_config(cfg)).replace(
        engine={"max_batch": 4}, refresh={"community_size": 64})
    return events, sc, params


def _service(world):
    _, sc, params = world
    return FraudService(sc, params=params).build()


@pytest.fixture
def no_persistent_cache():
    """A compile served from JAX's persistent cache is no backend compile;
    keep the counter's tests independent of whether one is configured."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def test_off_returns_the_shared_no_op_and_builds_no_annotation(monkeypatch):
    built = []
    monkeypatch.setattr(spans, "_annotation",
                        lambda *a, **kw: built.append(a))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **kw: built.append(a))
    a = spans.span("batch.flush", seq=1, n=4, trigger="size")
    b = spans.span("no.such.span")          # not even checked while off
    assert a is spans.OFF and b is spans.OFF
    with a:
        pass
    assert built == []


def test_an_unregistered_name_raises_when_on(spans_on):
    with pytest.raises(ValueError, match="unknown span"):
        spans.span("s2.lanuch")
    with spans.span("s2.launch") as s:
        assert s is not spans.OFF


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    names = set(spans.SPANS)
    out, meta = [], {}
    profile = ProfileData.from_file(path)
    for plane in profile.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns))
                        if e.name in ("batch.flush", "service.submit"):
                            meta.setdefault(e.name, []).append(dict(e.stats))
    return out, meta


def test_a_traced_streaming_run_emits_every_span_on_its_path(
        world, spans_on, tmp_path):
    events, _, _ = world
    svc = _service(world)
    svc.warmup()
    before = svc.stats().flushes
    with jax.profiler.trace(str(tmp_path)):
        for ev in events:
            svc.submit(ev)
        svc.drain()
    flushes = svc.stats().flushes - before
    assert svc.stats().refreshes > 0
    svc.close()
    evs, meta = _host_events(tmp_path)
    # the synchronous refresh on the inline backend reaches every span but
    # the slot gaps, which only an hgt model builds (tests/test_hgt.py)
    assert {e[0] for e in evs} == set(spans.SPANS) - {"s2.slot_dt"}
    by = {}
    for name, s, e in evs:
        by.setdefault(name, []).append((s, e))
    assert len(by["service.submit"]) == len(events)
    assert len(by["ingest.order"]) == len(events)
    # two batch.flush spans with one seq per flush the service counted:
    # the launch, and the resolve on a later call or at the drain; every
    # stage-2 span of the window nested inside one of them
    assert len(by["batch.flush"]) == 2 * flushes
    for name in ("batch.assemble", "kv.lookup", "s2.launch", "s2.sync",
                 "s2.tail", "batch.results"):
        assert len(by[name]) == flushes, name
        for s, e in by[name]:
            assert any(fs <= s and e <= fe for fs, fe in by["batch.flush"]), \
                name
    for name in ("refresh.snapshot", "refresh.stage1", "refresh.put"):
        for s, e in by[name]:
            assert any(rs <= s and e <= re for rs, re in by["refresh"]), name
    # the metadata joins an order's spans and a flush's: each event keeps
    # its bare name and carries the order id, or the flush's sequence
    # number, real size and trigger
    assert sorted(m["order_id"] for m in meta["service.submit"]) == \
        sorted(ev.order_id for ev in events)
    flush_meta = meta["batch.flush"]
    assert len({m["seq"] for m in flush_meta}) == flushes
    assert sum(m["n"] for m in flush_meta) == 2 * len(events)
    assert {m["trigger"] for m in flush_meta} <= {"size", "deadline",
                                                  "forced"}


def test_spans_on_leave_every_score_unchanged(world):
    events, _, _ = world

    def scores(on):
        spans.enable(on)
        try:
            svc = _service(world)
            out = [r for ev in events for r in svc.submit(ev)] + svc.drain()
            svc.close()
        finally:
            spans.enable(False)
        return {r.request.tag.order_id: r.score for r in out}

    assert scores(True) == scores(False)


def test_the_compile_counter_rises_on_a_fresh_bucket_only(
        world, no_persistent_cache, spans_on):
    _, sc, _ = world
    svc = _service(world)
    scorer = svc.engine.pool.workers[0].scorer
    feat_dim = sc.model.feat_dim

    def flush(b):
        scorer(np.zeros((b, feat_dim), np.float32), [[] for _ in range(b)])

    flush(2)                                # compiles bucket 2
    n0, by0 = svc.stats().compiles, spans.compiles_by_span()
    flush(2)                                # warmed: no compile
    assert svc.stats().compiles == n0
    flush(4)                                # a fresh bucket compiles
    assert svc.stats().compiles > n0
    by1 = spans.compiles_by_span()
    # charged to the innermost program span open: the stage-2 launch
    assert by1.get("s2.launch", 0) > by0.get("s2.launch", 0)
    svc.close()
