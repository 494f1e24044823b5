"""`repro.service`: config round-trip, lifecycle, facade equivalence with the
legacy entry points (bit-identical), versioned hot-swap, admission control."""
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LNNConfig, lnn_init
from repro.data import SynthConfig, generate_event_stream
from repro.service import (
    FraudService,
    ModelSection,
    ScoreRequest,
    ServiceConfig,
    ServiceLifecycleError,
)


@pytest.fixture(scope="module")
def service_world():
    events, g, _ = generate_event_stream(
        SynthConfig(num_users=70, num_rings=3, feature_noise=0.8, seed=7),
        rate_per_s=500.0,
    )
    cfg = LNNConfig(num_gnn_layers=3, hidden_dim=32,
                    feat_dim=g.order_features.shape[1])
    params = lnn_init(jax.random.PRNGKey(0), cfg)
    sc = ServiceConfig(model=ModelSection.from_lnn_config(cfg)).replace(
        engine={"max_batch": 8})
    return events, cfg, params, sc


def _legacy_engine(params, cfg, **engine_kw):
    from repro.stream import EngineConfig, StreamingEngine

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return StreamingEngine(params, cfg, EngineConfig(**engine_kw))


# ------------------------------------------------------------ ServiceConfig
def test_service_config_json_roundtrip(tmp_path):
    sc = ServiceConfig(
        mode="streaming",
        model=ModelSection(gnn_type="gat", hidden_dim=32, mlp_dims=(16, 8),
                           feat_dim=12),
    ).replace(
        engine={"num_workers": 4, "steal_threshold": 10, "max_history": None},
        store={"capacity": 1000, "ttl_seconds": 5.0},
        refresh={"refresh_every": 3, "async_refresh": True},
        admission={"max_queue_depth": 32, "policy": "block"},
    )
    assert ServiceConfig.from_json(sc.to_json()) == sc
    path = str(tmp_path / "svc.json")
    sc.save(path)
    loaded = ServiceConfig.load(path)
    assert loaded == sc
    # tuples survive the JSON list round-trip
    assert loaded.model.mlp_dims == (16, 8)
    assert isinstance(loaded.model.mlp_dims, tuple)
    # the artifact rebuilds the legacy configs exactly
    assert loaded.to_lnn_config().gnn_type == "gat"
    ecfg = loaded.to_engine_config()
    assert (ecfg.num_workers, ecfg.refresh_every, ecfg.store_capacity) == (4, 3, 1000)


def test_service_config_rejects_unknown_keys():
    with pytest.raises(ValueError, match="unknown key"):
        ServiceConfig.from_dict({"modle": "batch"})
    with pytest.raises(ValueError, match=r"ServiceConfig\.engine"):
        ServiceConfig.from_dict({"engine": {"max_batchh": 4}})
    with pytest.raises(ValueError, match=r"ServiceConfig\.admission"):
        ServiceConfig.from_dict({"admission": {"policy": "shed", "shed": 1}})
    # replace() applies the same rejection to section-dict overrides
    with pytest.raises(ValueError, match="unknown key"):
        ServiceConfig().replace(engine={"nope": 1})


def test_service_config_validation():
    with pytest.raises(ValueError, match="mode"):
        ServiceConfig(mode="realtime")
    with pytest.raises(ValueError, match="policy"):
        ServiceConfig.from_dict({"admission": {"policy": "drop"}})
    with pytest.raises(ValueError, match="num_workers"):
        ServiceConfig().replace(engine={"num_workers": 0})


# ---------------------------------------------------------------- lifecycle
def test_lifecycle_is_enforced(service_world):
    events, cfg, params, sc = service_world
    svc = FraudService(sc, params=params)
    assert svc.state == "created"
    with pytest.raises(ServiceLifecycleError, match="submit"):
        svc.submit(events[0])
    with pytest.raises(ServiceLifecycleError, match="warmup"):
        svc.warmup()
    svc.build()
    assert svc.state == "built"
    with pytest.raises(ServiceLifecycleError, match="build"):
        svc.build()
    svc.warmup()
    assert svc.state == "ready"
    out = svc.submit(events[0])
    assert svc.state == "serving"
    out += svc.drain()
    assert svc.state == "drained" and len(out) == 1
    svc.close()
    assert svc.state == "closed"
    svc.close()          # idempotent
    for op in (svc.drain, svc.warmup, lambda: svc.submit(events[0])):
        with pytest.raises(ServiceLifecycleError):
            op()
    with pytest.raises(ServiceLifecycleError, match="load_model"):
        svc.load_model(params)


def test_build_requires_a_model(service_world):
    _, _, params, sc = service_world
    svc = FraudService(sc)
    with pytest.raises(ServiceLifecycleError, match="load_model"):
        svc.build()
    svc.load_model(params)
    svc.build()
    assert svc.state == "built"


def test_mode_guards(service_world, small_communities):
    events, cfg, params, sc = service_world
    streaming = FraudService(sc, params=params).build()
    with pytest.raises(ServiceLifecycleError, match="mode='batch'"):
        streaming.refresh(small_communities)
    batch = FraudService(sc.replace(mode="batch"), params=params).build()
    with pytest.raises(ServiceLifecycleError, match="mode='streaming'"):
        batch.submit(events[0])


# ----------------------------------------------- facade equivalence (batch)
def test_batch_mode_bit_identical_to_lambda_pipeline(small_communities):
    """Acceptance: FraudService(mode='batch') scores == LambdaPipeline.score
    bitwise, over the same refreshed store contents."""
    from repro.serve import LambdaPipeline, history_requests

    feat_dim = small_communities[0].graph.features.shape[1]
    cfg = LNNConfig(num_gnn_layers=3, hidden_dim=32, feat_dim=feat_dim)
    params = lnn_init(jax.random.PRNGKey(2), cfg)

    with pytest.warns(DeprecationWarning, match="FraudService"):
        pipe = LambdaPipeline(params, cfg, k_max=8)
    pipe.refresh(small_communities)
    requests = history_requests(small_communities)
    assert requests
    ref = pipe.score(requests)

    sc = ServiceConfig(mode="batch", model=ModelSection.from_lnn_config(cfg))
    svc = FraudService(sc, params=params).build().warmup()
    svc.refresh(small_communities)
    out = svc.score(requests)
    got = np.asarray([r.score for r in out])
    np.testing.assert_array_equal(got, ref)
    assert all(r.admitted and r.model_version == 0 for r in out)
    # the facade proves the same split-equivalence bound — WITHOUT the
    # internal verification replay counting as served traffic
    before = svc.stats().requests
    assert svc.score_equivalence_check(small_communities) < 1e-4
    assert svc.stats().requests == before
    # legacy dict requests still accepted (shim compatibility)
    legacy = [{"features": r.features, "entity_keys": r.entity_keys}
              for r in requests[:4]]
    np.testing.assert_array_equal(
        np.asarray([r.score for r in svc.score(legacy)]), ref[:4])


def test_equivalence_check_unaffected_by_shed_admission(small_communities):
    """The internal verification replay must bypass admission: a shed policy
    that would NaN-out tail requests cannot fail the check spuriously."""
    feat_dim = small_communities[0].graph.features.shape[1]
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=16, feat_dim=feat_dim)
    params = lnn_init(jax.random.PRNGKey(0), cfg)
    svc = FraudService(
        ServiceConfig(mode="batch", model=ModelSection.from_lnn_config(cfg))
        .replace(admission={"max_queue_depth": 2, "policy": "shed"}),
        params=params).build()
    svc.refresh(small_communities)
    assert svc.score_equivalence_check(small_communities) < 1e-4


# ------------------------------------------- facade equivalence (streaming)
@pytest.mark.parametrize("num_workers", [1, 4])
def test_streaming_mode_bit_identical_to_engine(service_world, num_workers):
    """Acceptance: FraudService(mode='streaming').replay == legacy
    StreamingEngine.replay bitwise, for N=1 and N=4 workers."""
    events, cfg, params, sc = service_world
    ref = _legacy_engine(params, cfg, max_batch=8).replay(events)
    s_ref = ref.scores_by_order()

    svc = FraudService(
        sc.replace(engine={"max_batch": 8, "num_workers": num_workers}),
        params=params).build()
    rep = svc.replay(events)
    s = rep.scores_by_order()
    assert set(s) == set(s_ref)
    assert all(s[o] == s_ref[o] for o in s_ref)
    st = svc.stats()
    assert st.requests == len(events) and st.scored == len(events)
    assert st.shed == 0 and st.blocked == 0


def test_shadow_rescores_a_delivery_larger_than_max_batch(service_world):
    """A call may deliver more than ``max_batch`` responses (flushes
    resolved on a later call arrive together, and a drain returns every
    one owed): the shadow re-scores them in bucket-sized dispatches, and an
    identical-weights shadow still diverges by exactly 0.0."""
    events, cfg, params, sc = service_world
    svc = FraudService(sc.replace(engine={"max_batch": 4}),
                       params=params).build()
    svc.register_model(params, version=5)
    svc.enable_shadow(5, fraction=1.0, threshold=1e-12)
    out = [r for ev in events[:40] for r in svc.submit(ev)] + svc.drain()
    assert len(out) == 40
    assert svc.shadow_observe(out) == 40
    sh = svc.shadow_stats()
    assert sh["sampled"] == 40
    assert sh["divergence_max"] == 0.0 and sh["alerts"] == 0
    svc.close()


def test_replay_report_summary_single_latency_pass(service_world):
    events, cfg, params, sc = service_world
    svc = FraudService(sc, params=params).build()
    rep = svc.replay(events[:60])
    s = rep.summary()
    # percentiles and mean come from the same cached pass
    assert s["mean_latency_ms"] == rep.percentiles_ms()["mean"]
    assert set(rep.percentiles_ms()) == {"p50", "p95", "p99", "mean"}


# ----------------------------------------------------------------- hot-swap
def test_hot_swap_mid_stream_replay_parity(service_world):
    """Registering an identical-weights copy as a new version mid-stream
    must leave every score bit-identical, while the machinery visibly
    swaps: results flushed after the swap carry the new version, KV puts
    are re-stamped, and pre-swap embeddings read back as model-stale."""
    events, cfg, params, sc = service_world
    s_ref = _legacy_engine(params, cfg, max_batch=8).replay(events).scores_by_order()

    params_copy = jax.tree_util.tree_map(jnp.asarray, params)
    svc = FraudService(sc, params=params).build().warmup()
    out = []
    half = len(events) // 2
    for ev in events[:half]:
        out.extend(svc.submit(ev))
    assert svc.load_model(params_copy) == 1
    for ev in events[half:]:
        out.extend(svc.submit(ev))
    out.extend(svc.drain())

    scores = {r.request.tag.order_id: r.score for r in out}
    assert set(scores) == set(s_ref)
    assert all(scores[o] == s_ref[o] for o in s_ref)
    # both versions actually served flushes, in order: v0 then v1
    versions = [r.model_version for r in out]
    assert set(versions) == {0, 1}
    assert versions == sorted(versions)
    st = svc.stats()
    assert st.model_versions == (0, 1) and st.model_version == 1
    assert st.model_swaps == 1
    # post-swap reads of pre-swap embeddings were detected, not silent
    assert st.model_stale_reads > 0


def test_hot_swap_new_flushes_score_on_new_params(service_world):
    """With genuinely different params, flushes after the swap must score
    under the new model: their responses differ from the old model's and
    are stamped with the new version."""
    events, cfg, params, sc = service_world
    params2 = lnn_init(jax.random.PRNGKey(99), cfg)
    evs = events[:80]
    s_old = _legacy_engine(params, cfg, max_batch=8).replay(evs).scores_by_order()

    svc = FraudService(sc, params=params).build().warmup()
    out = []
    for ev in evs[:40]:
        out.extend(svc.submit(ev))
    svc.load_model(params2, version=7)
    for ev in evs[40:]:
        out.extend(svc.submit(ev))
    out.extend(svc.drain())
    new = [r for r in out if r.model_version == 7]
    assert new, "no flush scored under the swapped model"
    diffs = [abs(r.score - s_old[r.request.tag.order_id]) for r in new]
    assert max(diffs) > 0, "post-swap flushes still scored with old params"
    # swapping BACK reuses the registered version (and its jit cache)
    assert svc.load_model(params, version=0) == 0
    assert svc.model_versions() == (0, 7)


def test_refresh_driver_stamps_model_version(service_world):
    events, cfg, params, sc = service_world
    svc = FraudService(sc, params=params).build()
    for ev in events[:30]:
        svc.submit(ev)
    svc.load_model(jax.tree_util.tree_map(jnp.asarray, params), version=3)
    for ev in events[30:]:
        svc.submit(ev)
    svc.drain()
    versions = {svc.store.version_of(k) is not None
                for k in svc.store.keys()}
    assert versions == {True}
    entries = [svc.store.get_entry(k) for k in svc.store.keys()]
    assert entries  # store populated
    model_versions = {e.model_version
                      for shard in svc.store._shards for e in shard.values()}
    assert model_versions == {0, 3}, model_versions


# --------------------------------------------------------------- admission
def test_streaming_admission_shed_accounting(service_world):
    events, cfg, params, sc = service_world
    svc = FraudService(
        sc.replace(engine={"max_batch": 8, "num_workers": 2,
                           "service_model_s": 0.05},
                   admission={"max_queue_depth": 6, "policy": "shed"}),
        params=params).build()
    rep = svc.replay(events)
    st = svc.stats()
    assert st.shed > 0 and st.blocked == 0
    assert st.requests == len(events)
    assert st.shed + len(rep.results) == len(events)
    # shed never inflates the enforced cap
    assert st.queue_depth_peak <= 6
    # report only carries admitted scores; shed ones were NaN + flagged
    assert all(r.admitted for r in rep.results)


def test_streaming_admission_block_accounting(service_world):
    events, cfg, params, sc = service_world
    svc = FraudService(
        sc.replace(engine={"max_batch": 8, "num_workers": 2,
                           "service_model_s": 0.05},
                   admission={"max_queue_depth": 6, "policy": "block"}),
        params=params).build()
    rep = svc.replay(events)
    st = svc.stats()
    assert st.blocked > 0 and st.shed == 0
    # backpressure loses nothing
    assert len(rep.results) == len(events)
    assert {r.request.tag.order_id for r in rep.results} \
        == {ev.order_id for ev in events}
    # the cap is actually enforced: the block drain must keep freeing
    # capacity even when the reorder buffer withholds flushed results
    # (regression: the loop used to give up on an empty release)
    assert st.queue_depth_peak <= 6


def test_streaming_shed_response_shape(service_world):
    events, cfg, params, sc = service_world
    svc = FraudService(
        sc.replace(engine={"max_batch": 64, "max_wait_s": 1e9},
                   admission={"max_queue_depth": 1, "policy": "shed"}),
        params=params).build()
    out = []
    for ev in events[:3]:
        out.extend(svc.submit(ev))
    shed = [r for r in out if not r.admitted]
    assert len(shed) == 2           # first fills the queue, rest shed
    assert all(math.isnan(r.score) for r in shed)
    assert all(isinstance(r.request, ScoreRequest) for r in shed)


def test_batch_admission_shed_and_block(small_communities):
    feat_dim = small_communities[0].graph.features.shape[1]
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=16, feat_dim=feat_dim)
    params = lnn_init(jax.random.PRNGKey(0), cfg)
    from repro.serve import history_requests

    base = ServiceConfig(mode="batch", model=ModelSection.from_lnn_config(cfg))
    ref_svc = FraudService(base, params=params).build()
    ref_svc.refresh(small_communities)
    requests = history_requests(small_communities)[:30]
    ref = np.asarray([r.score for r in ref_svc.score(requests)])

    shed_svc = FraudService(
        base.replace(admission={"max_queue_depth": 10, "policy": "shed"}),
        params=params, store=ref_svc.store).build()
    out = shed_svc.score(requests)
    kept = [r for r in out if r.admitted]
    assert len(kept) == 10 and shed_svc.stats().shed == 20
    np.testing.assert_array_equal(np.asarray([r.score for r in kept]), ref[:10])

    block_svc = FraudService(
        base.replace(admission={"max_queue_depth": 16, "policy": "block"}),
        params=params, store=ref_svc.store).build()
    out = block_svc.score(requests)
    assert all(r.admitted for r in out)
    assert block_svc.stats().blocked == 14
    np.testing.assert_array_equal(np.asarray([r.score for r in out]), ref)


# -------------------------------------------------------- shims + artifacts
def test_deprecation_shims_importable_and_warn(service_world):
    events, cfg, params, sc = service_world
    from repro.serve import LambdaPipeline
    from repro.stream import EngineConfig, StreamingEngine

    with pytest.warns(DeprecationWarning, match="FraudService"):
        LambdaPipeline(params, cfg)
    with pytest.warns(DeprecationWarning, match="FraudService"):
        StreamingEngine(params, cfg, EngineConfig())


def test_stream_request_types_are_the_service_types():
    """One request/response vocabulary: the streaming engine's classes ARE
    the service-level ones (not parallel near-duplicates)."""
    from repro.service.types import ScoreRequest as SR, ScoreResponse as SP
    from repro.stream import ScoredResult
    from repro.stream import ScoreRequest as StreamSR

    assert StreamSR is SR
    assert ScoredResult is SP


def test_from_artifact_and_context_manager(service_world, tmp_path):
    events, cfg, params, sc = service_world
    path = str(tmp_path / "service.json")
    sc.save(path)
    with FraudService.from_artifact(path, params=params) as svc:
        svc.submit(events[0])
        svc.drain()
        assert svc.stats().scored == 1
    assert svc.state == "closed"


def test_stats_to_dict_is_json_safe(service_world):
    import json

    events, cfg, params, sc = service_world
    svc = FraudService(sc, params=params).build()
    svc.replay(events[:40])
    d = svc.stats().to_dict()
    json.dumps(d)        # must not raise
    assert d["mode"] == "streaming" and d["requests"] == 40


def test_service_stats_json_roundtrip():
    """Every counter survives to_dict -> JSON -> from_dict bit-for-bit: the
    gateway's /v1/stats and /metrics render from this ONE snapshot, so a
    field that doesn't round-trip is a field that silently falls off the
    wire.  The sample below must set EVERY dataclass field to a non-default
    value — adding a field without extending it fails here."""
    import dataclasses
    import json

    from repro.service import ServiceStats

    sample = ServiceStats(
        mode="streaming", state="serving", model_version=3,
        model_versions=(0, 3, 9), model_swaps=2, requests=100, scored=90,
        shed=7, blocked=5, block_timeouts=3, queue_depth=4,
        queue_depth_peak=12, in_flight_peak=2, flushes=31,
        deferred_flushes=27, blocking_resolves=2, refreshes=6,
        entities_written=250, model_stale_reads=11, store_size=420,
        rollbacks=1, last_good_version=0, compiles=5,
        scores_by_version={0: 40, 3: 50},
        shadow={"version": 9, "fraction": 0.5, "threshold": 0.25,
                "sampled": 45, "divergence_sum": 0.5, "divergence_max": 0.1,
                "last_divergence": 0.01, "alerts": 1, "alert_active": True},
        store_stats={"hits": 10, "model_stale_reads": 11},
        workers=[{"worker": 0, "queue_depth": 2, "flushes": 5,
                  "stolen_in": 1, "stolen_out": 0, "restarts": 0,
                  "alive": True}],
        extra={"pool": {"steals": 1}},
    )
    defaults = ServiceStats()
    for f in dataclasses.fields(ServiceStats):
        assert getattr(sample, f.name) != getattr(defaults, f.name), \
            f"test sample leaves ServiceStats.{f.name} at its default — " \
            "extend the sample so the round-trip exercises it"

    wire = json.loads(json.dumps(sample.to_dict()))
    back = ServiceStats.from_dict(wire)
    assert back == sample
    assert isinstance(back.model_versions, tuple)
    # JSON stringifies mapping keys; from_dict restores the int versions
    assert back.scores_by_version == {0: 40, 3: 50}
    with pytest.raises(ValueError, match="unknown key"):
        ServiceStats.from_dict({**wire, "scoredd": 1})

    # the live service produces the same lossless round-trip
    live = ServiceStats.from_dict(json.loads(json.dumps(sample.to_dict())))
    assert live.to_dict() == sample.to_dict()
    # the gateway renders the compile counter from the same snapshot
    from repro.gateway.server import service_metric_lines

    lines = service_metric_lines(wire)
    assert "repro_service_compiles_total 5" in lines
    assert "repro_service_deferred_flushes_total 27" in lines
    assert "repro_service_blocking_resolves_total 2" in lines


# ------------------------------------------------- bounded block-mode stalls
def test_block_admission_bounded_wait(service_world):
    """Regression: block-mode admission used to wait unboundedly (and then
    admit over-cap) when force-flushing the deepest queue freed nothing.
    ``admission.block_max_wait_s`` bounds the stall on the wall clock and
    sheds on timeout — counted in ``ServiceStats.block_timeouts``."""
    events, cfg, params, sc = service_world

    # zero budget: the stall times out immediately -> timed-out shed
    svc = FraudService(
        sc.replace(engine={"max_batch": 64, "max_wait_s": 1e9},
                   admission={"max_queue_depth": 1, "policy": "block",
                              "block_max_wait_s": 0.0}),
        params=params).build()
    out = [r for ev in events[:3] for r in svc.submit(ev)]
    shed = [r for r in out if not r.admitted]
    assert len(shed) == 2 and all(math.isnan(r.score) for r in shed)
    st = svc.stats()
    assert st.block_timeouts == 2 and st.shed == 2 and st.blocked == 2
    # the bounded block never admits over-cap
    assert st.queue_depth_peak <= 1

    # a generous budget behaves like classic block: force-flushes free
    # capacity, everything is admitted, nothing times out
    svc2 = FraudService(
        sc.replace(engine={"max_batch": 8, "num_workers": 2,
                           "service_model_s": 0.05},
                   admission={"max_queue_depth": 6, "policy": "block",
                              "block_max_wait_s": 30.0}),
        params=params).build()
    rep = svc2.replay(events)
    st2 = svc2.stats()
    assert len(rep.results) == len(events)
    assert st2.blocked > 0 and st2.block_timeouts == 0 and st2.shed == 0


def test_drain_to_depth_clock_semantics(service_world):
    """WorkerPool.drain_to_depth: a finite budget times the stall out on the
    injected clock even when a flush WOULD free capacity; budget=None keeps
    the legacy unbounded semantics (flush until below cap)."""
    events, cfg, params, sc = service_world
    sc = sc.replace(engine={"max_batch": 64, "max_wait_s": 1e9})

    def fill(svc, n=4):
        for ev in events[:n]:
            svc.submit(ev)
        return svc.engine.pool

    # budget expires before the first flush pass -> not admitted, queue kept
    svc = FraudService(sc, params=params).build()
    pool = fill(svc)
    depth0 = len(pool)
    assert depth0 == 4
    ticks = iter([0.0, 100.0])
    drained, admitted = pool.drain_to_depth(
        1, events[3].arrival, budget_s=5.0, clock=lambda: next(ticks))
    assert not admitted and drained == [] and len(pool) == depth0

    # same pool, no budget: the legacy path flushes down below the cap
    drained, admitted = pool.drain_to_depth(1, events[3].arrival, budget_s=None)
    assert admitted and len(drained) == depth0 and len(pool) == 0


def test_block_max_wait_validation():
    with pytest.raises(ValueError, match="block_max_wait_s"):
        ServiceConfig.from_dict(
            {"admission": {"policy": "block", "block_max_wait_s": -1.0}})
    # round-trips with the rest of the admission section
    sc = ServiceConfig().replace(
        admission={"policy": "block", "block_max_wait_s": 0.25})
    assert ServiceConfig.from_json(sc.to_json()).admission.block_max_wait_s == 0.25
