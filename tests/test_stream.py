"""Streaming serving engine: incremental DDS equivalence, micro-batch flush
policy, and the headline stage-equivalence claim — micro-batched speed-layer
scores match the monolithic ``lnn_forward`` on the same event stream."""
import jax
import numpy as np
import pytest

from repro.core import LNNConfig, lnn_forward, lnn_init
from repro.core.dds import IncrementalDDSBuilder, build_dds, check_no_future_leak
from repro.core.graph import pad_graph
from repro.data import SynthConfig, generate_event_stream
from repro.stream import (
    CheckoutEvent,
    DeferredScore,
    EngineConfig,
    MicroBatcher,
    ScoreRequest,
    StreamingEngine,
)


@pytest.fixture(scope="module")
def stream_world():
    events, g, split = generate_event_stream(
        SynthConfig(num_users=80, num_rings=3, feature_noise=0.8, seed=5),
        rate_per_s=500.0,
    )
    cfg = LNNConfig(num_gnn_layers=3, hidden_dim=32,
                    feat_dim=g.order_features.shape[1])
    params = lnn_init(jax.random.PRNGKey(0), cfg)
    return events, g, cfg, params


# ------------------------------------------------------- incremental DDS
@pytest.mark.parametrize("history,max_history",
                         [("all", None), ("all", 4), ("consecutive", None)])
def test_incremental_dds_matches_batch_build(stream_world, history, max_history):
    """The streaming ingest path must produce the exact padded graph the
    offline ``build_dds`` produces on the same transactions."""
    events, g, _, _ = stream_world
    b = IncrementalDDSBuilder(g.order_features.shape[1], history, max_history)
    for ev in events:
        b.add_order(ev.entities, ev.snapshot, ev.features, ev.label)
    inc = b.build()
    check_no_future_leak(inc)
    ref = build_dds(b.to_static(), history, max_history)
    pg_i = pad_graph(inc.coo, max_deg=16)
    pg_r = pad_graph(ref.coo, max_deg=16)
    for f in pg_i._fields:
        np.testing.assert_array_equal(getattr(pg_i, f), getattr(pg_r, f))
    assert inc.entity_snap_ids == ref.entity_snap_ids
    assert inc.last_hop == ref.last_hop


def test_incremental_builder_rejects_event_time_regression():
    b = IncrementalDDSBuilder(feat_dim=2)
    b.add_order([1], 3, np.zeros(2))
    with pytest.raises(ValueError):
        b.add_order([1], 2, np.zeros(2))


def test_entity_keys_strictly_past():
    b = IncrementalDDSBuilder(feat_dim=2)
    b.add_order([7], 1, np.zeros(2))
    b.add_order([7], 3, np.zeros(2))
    # same-snapshot activity never feeds the key list (no leak)
    assert b.entity_keys([7], 3) == [(7, 1)]
    assert b.entity_keys([7], 4) == [(7, 3)]
    assert b.entity_keys([7], 1) == []
    assert b.entity_keys([99], 5) == []     # cold entity


# ------------------------------------------------------- micro-batcher
def _const_score_fn(feats, key_lists):
    return np.full(feats.shape[0], 0.5), np.zeros(feats.shape[0], np.int32)


def _req(arrival, feat_dim=4):
    return ScoreRequest(features=np.zeros(feat_dim, np.float32),
                        entity_keys=[], arrival=arrival)


def test_microbatch_size_trigger():
    mb = MicroBatcher(_const_score_fn, max_batch=4, max_wait_s=10.0)
    out = []
    for i in range(3):
        out += mb.submit(_req(arrival=0.001 * i), now=0.001 * i)
    assert out == [] and len(mb) == 3
    out += mb.submit(_req(arrival=0.003), now=0.003)
    assert len(out) == 4 and len(mb) == 0
    assert mb.stats["size_flushes"] == 1
    assert all(r.batch_size == 4 for r in out)


def test_microbatch_deadline_trigger():
    mb = MicroBatcher(_const_score_fn, max_batch=64, max_wait_s=0.005)
    mb.submit(_req(arrival=1.000), now=1.000)
    assert mb.poll(now=1.004) == []                 # deadline not reached
    out = mb.poll(now=1.0051)
    assert len(out) == 1
    assert mb.stats["deadline_flushes"] == 1
    # flush is stamped at the deadline (timer semantics), so the recorded
    # wait is exactly max_wait even though the poll came later
    assert out[0].queued_s == pytest.approx(0.005)


def test_microbatch_padding_matches_unpadded_scores(stream_world):
    """Bucket padding must not perturb real rows' scores."""
    events, g, cfg, params = stream_world
    eng = StreamingEngine(params, cfg, EngineConfig(max_batch=8))
    eng.warmup()
    # fill the store so lookups return real embeddings
    for ev in events:
        eng.submit(ev)
    eng.flush()
    reqs = [r for r in (eng.ingester.builder.entity_keys(ev.entities, ev.snapshot)
                        for ev in events[-5:])]
    feats = np.stack([ev.features for ev in events[-5:]]).astype(np.float32)
    # batch of 5 pads to bucket 8; score one-by-one (bucket 1) as reference
    p5, _ = eng._score_batch(feats, reqs)
    p1 = np.concatenate(
        [eng._score_batch(feats[i:i + 1], [reqs[i]])[0] for i in range(5)]
    )
    np.testing.assert_allclose(p5, p1, atol=1e-6)


# ------------------------------------------- engine: the headline claim
def test_streaming_scores_match_monolithic_forward(stream_world):
    """Acceptance: replay ingest -> refresh -> micro-batched scoring equals
    the monolithic full-graph ``lnn_forward`` on the same events (fp tol)."""
    events, g, cfg, params = stream_world
    eng = StreamingEngine(params, cfg,
                          EngineConfig(max_batch=8, refresh_every=1, max_deg=32))
    report = eng.replay(events)
    assert len(report.results) == len(events)

    pg = pad_graph(eng.ingester.materialize().coo, max_deg=32)
    full = np.asarray(jax.nn.sigmoid(
        jax.jit(lambda p, gg: lnn_forward(p, cfg, gg))(params, pg)
    ))
    scores = report.scores_by_order()
    # builder order id == position in the event stream (arrival order)
    err = max(
        abs(scores[ev.order_id] - full[i]) for i, ev in enumerate(events)
    )
    assert err < 1e-4, err
    # refresh-every-window keeps the speed layer perfectly fresh
    assert report.staleness_summary()["max"] == 0
    assert eng.store.stats["misses"] == 0


def test_streaming_staleness_grows_with_refresh_interval(stream_world):
    events, g, cfg, params = stream_world
    fresh = StreamingEngine(params, cfg, EngineConfig(max_batch=8, refresh_every=1))
    lazy = StreamingEngine(params, cfg, EngineConfig(max_batch=8, refresh_every=6))
    s_fresh = fresh.replay(events).staleness_summary()
    s_lazy = lazy.replay(events).staleness_summary()
    assert s_fresh["stale_frac"] == 0.0
    assert s_lazy["stale_frac"] > 0.0
    assert lazy.refresher.stats["refreshes"] < fresh.refresher.stats["refreshes"]


def test_async_refresh_drains_and_scores_everything(stream_world):
    events, g, cfg, params = stream_world
    eng = StreamingEngine(params, cfg,
                          EngineConfig(max_batch=8, async_refresh=True))
    report = eng.replay(events)
    assert len(report.results) == len(events)
    assert eng.refresher.stats["refreshes"] > 0


# ------------------------------------------------ refresh driver (regressions)
def _tiny_driver(refresh_every=1, async_mode=False, seed=0):
    from repro.serve.kvstore import KVStore
    from repro.stream.ingest import StreamIngester
    from repro.stream.refresh import RefreshDriver

    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=8, feat_dim=4)
    params = lnn_init(jax.random.PRNGKey(seed), cfg)
    ing = StreamIngester(4)
    store = KVStore(cfg.hidden_dim)
    drv = RefreshDriver(params, cfg, store, ing,
                        refresh_every=refresh_every, async_mode=async_mode)
    return drv, ing, store, cfg


def _tiny_event(snapshot, entity=1, arrival=0.0):
    return CheckoutEvent(order_id=-1, snapshot=snapshot, entities=(entity,),
                         features=np.zeros(4, np.float32), label=0.0,
                         arrival=arrival)


def test_async_refresh_inflight_list_stays_bounded():
    """Regression: completed futures must be pruned on every window-close
    hook — before, ``_inflight`` grew by one per refresh until ``drain()``,
    an unbounded leak over an unbounded stream."""
    from concurrent.futures import wait

    drv, ing, _, _ = _tiny_driver(async_mode=True)
    rounds = 6
    for t in range(rounds):
        res = ing.ingest(_tiny_event(t, entity=t % 3))
        drv.on_windows_closed(res.closed_window)
        wait(drv._inflight)          # every submitted refresh completes...
    assert drv.stats["refreshes"] >= rounds - 2
    # ...so at most the one submitted after the last prune remains tracked
    assert len(drv._inflight) <= 1
    drv.drain()
    assert drv._inflight == []


def test_failed_async_refresh_surfaces_its_error():
    """Regression: pruning finished futures used to drop them unread, so a
    stage-1 refresh that raised on a background thread vanished and the
    run went on as if the batch layer had written its embeddings."""
    from concurrent.futures import wait

    drv, ing, _, _ = _tiny_driver(async_mode=True)

    def broken_stage1(pgs, hints, model_version):
        raise FloatingPointError("stage 1 failed on the device")

    drv.stage1_executor = broken_stage1
    ing.ingest(_tiny_event(0))
    res = ing.ingest(_tiny_event(1))               # closes window 0
    assert drv.on_windows_closed(res.closed_window) is True
    wait(drv._inflight)                            # ...and its refresh failed
    res = ing.ingest(_tiny_event(2))
    with pytest.raises(FloatingPointError, match="stage 1 failed"):
        drv.on_windows_closed(res.closed_window)
    with pytest.raises(FloatingPointError, match="stage 1 failed"):
        drv.drain()


def test_refresh_cadence_carries_sparse_window_remainder():
    """Regression: a sparse snapshot jump (+5 windows, refresh_every=2) used
    to reset the counter to 0, silently swallowing the overshoot; the
    remainder must carry so long-run cadence stays refresh_every."""
    drv, _, _, _ = _tiny_driver(refresh_every=2)
    assert drv.on_windows_closed((0, 4)) is True       # +5 -> fires
    assert drv._windows_since_refresh == 1             # 5 % 2 carried
    assert drv.on_windows_closed((5, 5)) is True       # 1 + 1 -> fires
    assert drv.on_windows_closed((6, 6)) is False      # 0 + 1 -> waits
    assert drv.on_windows_closed((7, 7)) is True


def test_sync_refresh_snapshots_model_before_graph():
    """Regression: sync ``refresh()`` must capture (params, model_version)
    as one pair under the lock BEFORE snapshotting the graph — a hot-swap
    landing mid-snapshot may not retag the already-started refresh."""
    drv, ing, store, cfg = _tiny_driver()
    params_b = lnn_init(jax.random.PRNGKey(9), cfg)
    ing.ingest(_tiny_event(0))
    ing.ingest(_tiny_event(1))                          # closes window 0

    orig = drv._snapshot_graph

    def hook(up_to):
        drv.set_model(params_b, 7)                      # swap mid-snapshot
        return orig(up_to)

    drv._snapshot_graph = hook
    out = drv.refresh(0)
    assert out["entities_written"] == 1
    entries = [e for shard in store._shards for e in shard.values()]
    # old pair throughout: pre-swap version stamp AND pre-swap params
    assert all(e.model_version == 0 for e in entries)
    ref_drv, ref_ing, ref_store, _ = _tiny_driver()
    ref_ing.ingest(_tiny_event(0))
    ref_ing.ingest(_tiny_event(1))
    ref_drv.refresh(0)
    ref = [e for shard in ref_store._shards for e in shard.values()]
    np.testing.assert_array_equal(entries[0].value, ref[0].value)


def test_microbatcher_default_clock_is_monotonic_and_injectable():
    """Deadline scheduling runs on an injectable monotonic clock when the
    caller supplies no ``now`` — never the NTP-steppable wall clock."""
    import time as _time

    t = {"now": 100.0}
    mb = MicroBatcher(_const_score_fn, max_batch=8, max_wait_s=0.005,
                      clock=lambda: t["now"])
    mb.submit(_req(arrival=t["now"]))          # no explicit now: clock used
    assert mb.poll() == []                     # deadline not reached
    t["now"] += 0.004
    assert mb.poll() == []
    t["now"] += 0.002                          # past deadline
    out = mb.poll()
    assert len(out) == 1 and mb.stats["deadline_flushes"] == 1
    assert out[0].queued_s == pytest.approx(0.005)
    assert MicroBatcher(_const_score_fn).clock is _time.monotonic


def test_streaming_fused_stage2_matches_unfused(stream_world):
    """Flipping ``LNNConfig.use_pallas`` swaps the speed layer onto the fused
    Pallas stage-2 kernel (interpret mode on CPU); every replayed score must
    be identical to the unfused engine's, across all bucket shapes."""
    import dataclasses

    events, g, cfg, params = stream_world
    evs = events[:60]
    ref = StreamingEngine(params, cfg, EngineConfig(max_batch=8))
    s_ref = ref.replay(evs).scores_by_order()
    fused = StreamingEngine(params, dataclasses.replace(cfg, use_pallas=True),
                            EngineConfig(max_batch=8))
    s_fused = fused.replay(evs).scores_by_order()
    assert set(s_fused) == set(s_ref)
    err = max(abs(s_fused[o] - s_ref[o]) for o in s_ref)
    assert err < 1e-5, err


# ------------------------------------------------ flush/drain race (regression)
def test_deadline_flush_racing_concurrent_drain_is_empty_noop():
    """A deadline flush may race a concurrent drain of the same queue (work
    stealing, another thread's flush).  The loser must emit nothing: no
    zero-row score_fn call, no phantom deadline_flushes count."""
    calls = []

    def score_fn(feats, key_lists):
        calls.append(feats.shape[0])
        return np.full(feats.shape[0], 0.5), np.zeros(feats.shape[0], np.int32)

    mb = MicroBatcher(score_fn, max_batch=8, max_wait_s=0.005)
    mb.submit(_req(arrival=1.0), now=1.0)
    dl = mb.deadline()
    assert dl == pytest.approx(1.005)               # trigger armed...
    stolen = mb.take(1)                             # ...queue drained under it
    assert len(stolen) == 1
    out = mb.flush(dl)                  # the armed trigger fires on empty queue
    assert out == []
    assert calls == []                              # score_fn never saw 0 rows
    assert mb.stats["deadline_flushes"] == 0
    assert mb.stats["flushes"] == 0
    assert mb.stats["empty_flushes"] == 1
    assert mb.poll(now=2.0) == []                   # re-poll: nothing queued
    # the queue still works afterwards
    out = mb.submit(_req(arrival=3.0), now=3.0) + mb.poll(now=3.1)
    assert len(out) == 1 and mb.stats["deadline_flushes"] == 1


# ------------------------------------------- multi-worker replay parity
@pytest.mark.parametrize("backend", ["inline", "process"])
@pytest.mark.parametrize("num_workers", [1, 2, 4])
def test_replay_parity_nworkers_bit_identical(stream_world, num_workers,
                                              backend):
    """Acceptance: N-worker WorkerPool scores are BIT-identical to the
    single-worker StreamingEngine for N in {1, 2, 4} — same events, same
    refresh cadence, arbitrary per-worker flush interleavings — for BOTH
    the inline backend and the process backend (each worker a real OS
    process owning its KV shard)."""
    events, g, cfg, params = stream_world
    ref = StreamingEngine(params, cfg, EngineConfig(max_batch=8))
    s_ref = ref.replay(events).scores_by_order()
    eng = StreamingEngine(params, cfg,
                          EngineConfig(max_batch=8, num_workers=num_workers,
                                       backend=backend))
    try:
        rep = eng.replay(events)
    finally:
        eng.close()
    s = rep.scores_by_order()
    assert set(s) == set(s_ref)
    assert all(s[o] == s_ref[o] for o in s_ref), \
        max(abs(s[o] - s_ref[o]) for o in s_ref)
    if num_workers > 1:
        # the queue really sharded: more than one worker served traffic
        served = [w for w in rep.summary()["workers"] if w["requests"] > 0]
        assert len(served) > 1


def test_replay_parity_under_randomized_flush_interleavings(stream_world):
    """Bit-parity must hold for ANY flush interleaving: randomize every
    knob that changes when and how flushes fire (deadline, batch size,
    virtual service occupancy, stealing) and replay against the
    single-worker reference."""
    events, g, cfg, params = stream_world
    evs = events[:150]
    ref = StreamingEngine(params, cfg, EngineConfig(max_batch=8))
    s_ref = ref.replay(evs).scores_by_order()
    rng = np.random.default_rng(0)
    for trial in range(4):
        ecfg = EngineConfig(
            num_workers=int(rng.integers(2, 5)),
            max_batch=int(rng.choice([4, 8, 16])),
            max_wait_s=float(rng.choice([0.001, 0.005, 0.02])),
            service_model_s=float(rng.choice([0.0, 0.01, 0.05])),
            steal_threshold=int(rng.choice([6, 10])),
        )
        s = StreamingEngine(params, cfg, ecfg).replay(evs).scores_by_order()
        assert set(s) == set(s_ref)
        assert all(s[o] == s_ref[o] for o in s_ref), (trial, ecfg)


def test_engine_flush_hands_back_every_flush_in_flight(stream_world):
    """Flushes kept in flight across calls score bit for bit as flushes
    finished inside the call that launched them, arrive in submission
    order, and the engine's flush leaves none in flight."""
    events, g, cfg, params = stream_world
    evs = events[:120]
    scores, stats = {}, {}
    for mode in ("sync", "held"):
        eng = StreamingEngine(params, cfg,
                              EngineConfig(max_batch=8, num_workers=2))
        for w in eng.pool.workers:
            score = w.batcher.score_fn
            if mode == "sync":
                # a finished tuple: the batcher's synchronous path
                w.batcher.score_fn = lambda f, k, s=score: s(f, k).wait()
            else:
                # never ready: each flush waits for its worker's next
                # launch or for the barrier
                w.batcher.score_fn = lambda f, k, s=score: DeferredScore(
                    s(f, k).wait, ready=lambda: False)
        results = []
        for ev in evs:
            results.extend(eng.submit(ev))
        assert any(w.inflight is not None for w in eng.pool.workers) \
            == (mode == "held")
        results.extend(eng.flush())
        assert all(w.inflight is None for w in eng.pool.workers)
        assert [r.request.seq for r in results] == list(range(len(evs)))
        scores[mode] = {r.request.tag.order_id: r.score for r in results}
        stats[mode] = eng.pool.stats
    assert scores["held"] == scores["sync"]
    assert stats["sync"]["deferred_flushes"] == 0
    assert stats["held"]["deferred_flushes"] > 0
    assert stats["held"]["blocking_resolves"] == stats["held"]["flushes"]


def test_multiworker_results_arrive_in_submission_order(stream_world):
    events, g, cfg, params = stream_world
    eng = StreamingEngine(params, cfg, EngineConfig(max_batch=8, num_workers=4))
    rep = eng.replay(events[:120])
    seqs = [r.request.seq for r in rep.results]
    assert seqs == sorted(seqs) == list(range(len(seqs)))


def test_multiworker_work_stealing_preserves_scores(stream_world):
    """Drive a slow-worker scenario (virtual service model) so shards back
    up and stealing engages; scores must still match the reference."""
    events, g, cfg, params = stream_world
    evs = events[:150]
    ref = StreamingEngine(params, cfg, EngineConfig(max_batch=8))
    s_ref = ref.replay(evs).scores_by_order()
    eng = StreamingEngine(params, cfg, EngineConfig(
        max_batch=8, num_workers=4, service_model_s=0.05, steal_threshold=10))
    rep = eng.replay(evs)
    s = rep.scores_by_order()
    assert eng.pool.pool_stats["steals"] > 0
    assert all(s[o] == s_ref[o] for o in s_ref)
    # stolen requests really were served off their affine worker
    off_affine = [r for r in rep.results
                  if r.worker != eng.pool.router.route(r.request.entity_keys)]
    assert 0 < len(off_affine) <= eng.pool.pool_stats["stolen_requests"]


def test_live_pool_reshard_preserves_scores_and_affinity(stream_world):
    """Resharding a live pool mid-stream (drain -> router+store+workers
    migrate together) keeps scores bit-identical and the affinity contract
    intact; resharding the router alone is caught, never silent."""
    events, g, cfg, params = stream_world
    ref = StreamingEngine(params, cfg, EngineConfig(max_batch=8))
    s_ref = ref.replay(events).scores_by_order()

    eng = StreamingEngine(params, cfg, EngineConfig(max_batch=8, num_workers=2))
    eng.warmup()
    results = []
    half = len(events) // 2
    for ev in events[:half]:
        results.extend(eng.submit(ev))
    results.extend(eng.pool.reshard(4))       # drained under the old topology
    assert eng.pool.num_workers == 4 and len(eng.pool.workers) == 4
    assert eng.store.num_shards == 4          # store migrated with the router
    from repro.serve.kvstore import pack_key
    for ent in range(50):
        assert (eng.store.shard_of(pack_key(ent, 0))
                == eng.pool.router.worker_of(ent))
    for ev in events[half:]:
        results.extend(eng.submit(ev))
    results.extend(eng.flush())
    scores = {r.request.tag.order_id: r.score for r in results}
    assert set(scores) == set(s_ref)
    assert all(scores[o] == s_ref[o] for o in s_ref)

    # router resharded out from under the pool -> loud failure, not silence
    # (both directions: grown past the pool and shrunk below it)
    for n0, n1 in ((2, 8), (4, 2)):
        bad = StreamingEngine(params, cfg,
                              EngineConfig(max_batch=8, num_workers=n0))
        bad.pool.router.reshard(n1)
        with pytest.raises(RuntimeError, match="WorkerPool.reshard"):
            bad.submit(events[0])


def test_engine_cold_start_scores_without_history():
    """First-ever events (empty store, no history) must score, not crash."""
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=16, feat_dim=4)
    params = lnn_init(jax.random.PRNGKey(1), cfg)
    eng = StreamingEngine(params, cfg, EngineConfig(max_batch=2, max_wait_s=0.001))
    evs = [CheckoutEvent(order_id=i, snapshot=0, entities=(i, 100 + i),
                         features=np.zeros(4, np.float32), label=0.0,
                         arrival=0.001 * i) for i in range(3)]
    out = []
    for ev in evs:
        out += eng.submit(ev)
    out += eng.flush()
    assert len(out) == 3
    assert all(np.isfinite(r.score) for r in out)
    assert all(r.staleness == -1 for r in out)      # nothing served from KV
