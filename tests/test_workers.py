"""Multi-worker sharded speed layer: router key-affinity, rendezvous
minimal movement, explicit-reshard-only semantics, work stealing, the
reorder collector, per-worker flush independence, and flushes kept in
flight across calls."""
import jax
import numpy as np
import pytest

from repro.core import LNNConfig, lnn_init
from repro.dist.sharding import rendezvous_shard, splitmix64, stable_shard
from repro.serve.kvstore import KVStore, entity_shard, pack_key
from repro.stream import (
    DeferredScore,
    MicroBatcher,
    ScoreRequest,
    ShardRouter,
    WorkerPool,
)
from repro.stream.workers import (
    ScoresInFlight,
    SpeedLayerWorker,
    Stage2Scorer,
    _ReorderBuffer,
)


# ------------------------------------------------------------------ hashing
def test_splitmix64_avalanches_consecutive_keys():
    outs = {splitmix64(i) for i in range(1000)}
    assert len(outs) == 1000
    # avalanche: consecutive inputs land in different 32-bit high halves
    highs = {splitmix64(i) >> 32 for i in range(1000)}
    assert len(highs) > 990


def test_stable_and_rendezvous_shards_cover_all_buckets():
    for n in (2, 3, 8):
        assert {stable_shard(k, n) for k in range(500)} == set(range(n))
        assert {rendezvous_shard(k, n) for k in range(500)} == set(range(n))


def test_rendezvous_minimal_movement():
    """Growing n -> n+1 moves only keys that land on the NEW shard — no key
    migrates between surviving shards (the property that makes explicit
    resharding cheap for warm workers)."""
    keys = range(2000)
    for n in (1, 2, 4, 7):
        before = {k: rendezvous_shard(k, n) for k in keys}
        after = {k: rendezvous_shard(k, n + 1) for k in keys}
        moved = [k for k in keys if before[k] != after[k]]
        assert all(after[k] == n for k in moved)
        # roughly 1/(n+1) of keys move (loose bounds, fixed key set)
        frac = len(moved) / len(before)
        assert 0.3 / (n + 1) < frac < 2.5 / (n + 1)


# ------------------------------------------------------------------- router
def test_router_matches_entity_affine_store():
    """The affinity contract: a request routed to worker w only ever needs
    KV reads for its primary entity from shard w of an entity-affine store
    with num_shards == num_workers."""
    n = 4
    router = ShardRouter(n)
    store = KVStore(dim=2, num_shards=n, shard_by_entity=True)
    for ent in range(200):
        w = router.worker_of(ent)
        for t in (0, 3, 17):
            assert store.shard_of(pack_key(ent, t)) == w
        assert entity_shard(ent, n) == w


def test_router_routes_by_primary_entity_and_pins_cold_requests():
    router = ShardRouter(3)
    keys = [(42, 5), (99, 2)]
    assert router.route(keys) == router.worker_of(42)
    assert router.route([]) == 0


def test_router_worker_count_changes_only_via_reshard():
    router = ShardRouter(2)
    with pytest.raises(AttributeError):
        router.num_workers = 5
    assert router.num_workers == 2 and router.epoch == 0
    before = {e: router.worker_of(e) for e in range(100)}
    epoch = router.reshard(3)
    assert epoch == 1 and router.num_workers == 3
    after = {e: router.worker_of(e) for e in range(100)}
    moved = [e for e in before if before[e] != after[e]]
    assert moved, "resharding 2 -> 3 must move some entities"
    assert all(after[e] == 2 for e in moved)   # rendezvous: all to new worker
    with pytest.raises(ValueError):
        router.reshard(0)


# ----------------------------------------------------- router property tests
def _router_property_tests():
    pytest.importorskip("hypothesis", reason="hypothesis not installed")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=200, deadline=None)
    @given(entity=st.integers(min_value=0, max_value=2**40),
           n=st.integers(min_value=1, max_value=16))
    def affinity_is_instance_independent(entity, n):
        """route() is a pure function of (entity, worker count): any two
        routers with the same count agree — affinity never drifts with
        router lifetime, construction order, or prior traffic."""
        a, b = ShardRouter(n), ShardRouter(n)
        assert a.worker_of(entity) == b.worker_of(entity)
        assert a.worker_of(entity) == entity_shard(entity, n)
        assert 0 <= a.worker_of(entity) < n

    @settings(max_examples=100, deadline=None)
    @given(entities=st.lists(st.integers(min_value=0, max_value=2**40),
                             min_size=1, max_size=50),
           n=st.integers(min_value=1, max_value=8),
           grow=st.integers(min_value=1, max_value=4))
    def mapping_changes_only_through_reshard(entities, n, grow):
        """Without reshard() the mapping is frozen; after reshard(n + grow)
        it equals a fresh router's at the new count, and every moved entity
        lands on one of the added workers (rendezvous minimal movement)."""
        router = ShardRouter(n)
        before = [router.worker_of(e) for e in entities]
        # re-querying never changes anything (no hidden rebalancing)
        assert [router.worker_of(e) for e in entities] == before
        router.reshard(n + grow)
        fresh = ShardRouter(n + grow)
        after = [router.worker_of(e) for e in entities]
        assert after == [fresh.worker_of(e) for e in entities]
        for b, a in zip(before, after):
            assert a == b or a >= n

    affinity_is_instance_independent()
    mapping_changes_only_through_reshard()


def test_router_affinity_properties():
    _router_property_tests()


# ---------------------------------------------------------- reorder buffer
def _result(seq, score=0.5):
    from repro.stream.microbatch import ScoredResult

    req = ScoreRequest(features=np.zeros(2, np.float32), entity_keys=[],
                       arrival=0.0, seq=seq)
    return ScoredResult(request=req, score=score, staleness=-1,
                        queued_s=0.0, service_s=0.0, batch_size=1)


def test_reorder_buffer_releases_in_submission_order():
    rb = _ReorderBuffer()
    rb.add([_result(2), _result(1)])
    assert rb.release() == []                 # seq 0 still missing
    rb.add([_result(0)])
    out = rb.release()
    assert [r.request.seq for r in out] == [0, 1, 2]
    rb.add([_result(3)])
    assert [r.request.seq for r in rb.release()] == [3]
    assert len(rb) == 0


# ------------------------------------------------------------ worker/steal
def _const_score_fn(feats, key_lists):
    return np.full(feats.shape[0], 0.5), np.zeros(feats.shape[0], np.int32)


def _req(arrival, seq=-1, feat_dim=4, keys=()):
    return ScoreRequest(features=np.zeros(feat_dim, np.float32),
                        entity_keys=list(keys), arrival=arrival, seq=seq)


def test_worker_defers_flush_while_virtually_busy():
    """With a virtual service model, a size-triggered flush opens a service
    window; the next flush waits for it, so the queue backs up past
    max_batch — the condition work stealing exists for."""
    w = SpeedLayerWorker(0, _const_score_fn, max_batch=2, max_wait_s=10.0,
                         service_model_s=1.0)
    for i in range(6):
        w.enqueue(_req(arrival=0.1 * i, seq=i))
    out = w.pump(now=0.5)
    assert len(out) == 2                      # first batch served...
    assert w.busy_until == pytest.approx(1.1)  # trigger 0.1 + service 1.0
    assert len(w) == 4                        # ...rest deferred (backed up)
    out = w.pump(now=0.6)
    assert out == []                          # still busy
    out = w.pump(now=1.2)
    assert len(out) == 2 and len(w) == 2      # freed: one more batch
    assert w.stats["max_queue_depth"] == 6


def test_pool_steals_from_backed_up_shard():
    """An idle worker with an empty queue takes the oldest half of a
    backed-up victim's queue and serves it."""
    pool = WorkerPool.__new__(WorkerPool)   # bypass jit-scorer construction
    pool.router = ShardRouter(2)
    pool.max_batch = 2
    pool.steal_threshold = 3
    pool.workers = [
        SpeedLayerWorker(0, _const_score_fn, max_batch=2, max_wait_s=10.0,
                         service_model_s=5.0),
        SpeedLayerWorker(1, _const_score_fn, max_batch=2, max_wait_s=10.0,
                         service_model_s=5.0),
    ]
    pool._reorder = _ReorderBuffer()
    pool._seq = 0
    pool.pool_stats = {"steals": 0, "stolen_requests": 0, "routed": 0}
    victim, thief = pool.workers
    for i in range(6):
        victim.enqueue(_req(arrival=0.01 * i, seq=i))
    victim.busy_until = 100.0                 # victim stuck mid-service
    out = pool.poll(now=1.0)
    assert pool.pool_stats["steals"] == 1
    assert pool.pool_stats["stolen_requests"] == 3   # half of 6
    assert thief.stats["stolen_in"] == 3
    assert victim.stats["stolen_out"] == 3
    # thief size-flushed the first stolen batch immediately, in seq order
    assert [r.request.seq for r in out] == [0, 1]
    assert all(r.worker == 1 for r in out)
    assert len(victim) == 3 and len(thief) == 1
    # stamps floor at the steal time: the work could not have been served
    # before it reached the thief, so waits are not backdated to the
    # victim's long-missed triggers
    assert all(r.queued_s == pytest.approx(1.0 - r.request.arrival) for r in out)


def test_pool_does_not_steal_below_threshold():
    pool = WorkerPool.__new__(WorkerPool)
    pool.router = ShardRouter(2)
    pool.max_batch = 4
    pool.steal_threshold = 8
    pool.workers = [
        SpeedLayerWorker(0, _const_score_fn, max_batch=4, max_wait_s=10.0,
                         service_model_s=5.0),
        SpeedLayerWorker(1, _const_score_fn, max_batch=4, max_wait_s=10.0,
                         service_model_s=5.0),
    ]
    pool._reorder = _ReorderBuffer()
    pool._seq = 0
    pool.pool_stats = {"steals": 0, "stolen_requests": 0, "routed": 0}
    victim = pool.workers[0]
    for i in range(5):
        victim.enqueue(_req(arrival=0.01 * i, seq=i))
    victim.busy_until = 100.0
    pool.poll(now=1.0)
    assert pool.pool_stats["steals"] == 0 and len(victim) == 5


# ------------------------------------------------- microbatcher primitives
def test_take_steals_oldest_requests_atomically():
    mb = MicroBatcher(_const_score_fn, max_batch=8, max_wait_s=10.0)
    for i in range(5):
        mb.enqueue(_req(arrival=0.1 * i, seq=i))
    stolen = mb.take(2)
    assert [r.seq for r in stolen] == [0, 1]
    assert len(mb) == 3 and mb.stats["stolen"] == 2
    assert mb.oldest_arrival == pytest.approx(0.2)
    assert mb.take(0) == []
    assert len(mb.take(99)) == 3 and len(mb) == 0


# --------------------------------------------------- flushes kept in flight
class _Flight:
    """Scorer double: every flush answers with a DeferredScore whose
    ``ready()`` the test controls (``landed``), and whose score is each
    row's first feature (the request's seq, so the pairing is checked).
    ``ready=None`` makes it answer as the process backend does."""

    def __init__(self, controlled: bool = True):
        self.controlled = controlled
        self.landed = False
        self.waits = 0

    def __call__(self, feats, key_lists):
        probs = feats[:, 0].copy()
        stale = np.zeros(len(feats), np.int32)

        def wait():
            self.waits += 1
            return probs, stale, 0

        ready = (lambda: self.landed) if self.controlled else None
        return DeferredScore(wait, ready=ready)


def _flight_pool(num_workers=1, max_batch=2, controlled=True):
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=4, feat_dim=4, mlp_dims=(4,))
    store = KVStore(dim=4, num_shards=num_workers,
                    shard_by_entity=num_workers > 1)
    pool = WorkerPool(lnn_init(jax.random.PRNGKey(0), cfg), cfg, store,
                      num_workers=num_workers, max_batch=max_batch,
                      max_wait_s=10.0)
    flight = _Flight(controlled)
    for w in pool.workers:
        w.batcher.score_fn = flight
    return pool, flight


def _order(i, entity=0):
    f = np.zeros(4, np.float32)
    f[0] = i
    return ScoreRequest(features=f, entity_keys=[(entity, 0)],
                        arrival=0.001 * i)


def _in_flight(pool):
    return [w.wid for w in pool.workers if w.inflight is not None]


def test_a_flush_comes_back_on_a_later_call_once_ready():
    pool, flight = _flight_pool()
    assert pool.submit(_order(0), 0.0) == []
    # the size flush launches inside this call and stays in flight
    assert pool.submit(_order(1), 0.001) == []
    assert _in_flight(pool) == [0] and pool.in_flight() == 2
    assert pool.poll(0.002) == []              # not ready: nothing blocks
    assert pool.submit(_order(2), 0.002) == []
    assert flight.waits == 0
    flight.landed = True
    out = pool.poll(0.003)
    assert [r.request.seq for r in out] == [0, 1]
    assert [r.score for r in out] == [0.0, 1.0]
    assert _in_flight(pool) == [] and pool.in_flight() == 0
    assert pool.pool_stats["deferred_flushes"] == 1
    assert pool.pool_stats["blocking_resolves"] == 0
    assert pool.stats["flushes"] == 1


def test_a_second_launch_on_the_same_worker_resolves_the_older_flush():
    pool, flight = _flight_pool()
    for i in range(3):
        assert pool.submit(_order(i), 0.001 * i) == []
    # the second launch resolves the first flush after it, waiting for it
    out = pool.submit(_order(3), 0.003)
    assert [r.request.seq for r in out] == [0, 1]
    assert flight.waits == 1 and _in_flight(pool) == [0]
    assert pool.pool_stats["blocking_resolves"] == 1
    assert pool.pool_stats["deferred_flushes"] == 1
    # one flush in flight per worker: with two workers, each keeps its own
    pool, flight = _flight_pool(num_workers=2)
    ents = [e for e in range(64) if pool.router.worker_of(e) == 0][:1] + \
        [e for e in range(64) if pool.router.worker_of(e) == 1][:1]
    seq = 0
    for ent in ents:
        for _ in range(2):
            pool.submit(_order(seq, ent), 0.001 * seq)
            seq += 1
    assert _in_flight(pool) == [0, 1] and flight.waits == 0


@pytest.mark.parametrize("barrier", ["flush", "reshard", "drain_to_depth",
                                     "settle"])
def test_every_barrier_leaves_nothing_in_flight(barrier):
    pool, flight = _flight_pool()
    out = []
    for i in range(5):          # two flushes launched, one order queued
        out.extend(pool.submit(_order(i), 0.001 * i))
    assert _in_flight(pool) == [0] and len(pool) == 1
    if barrier == "flush":
        out.extend(pool.flush())
    elif barrier == "reshard":
        out.extend(pool.reshard(2))
    elif barrier == "drain_to_depth":
        got, admitted = pool.drain_to_depth(1, 0.005)
        out.extend(got)
        assert admitted
    else:
        pool.settle()
        assert _in_flight(pool) == []
        out.extend(pool.flush())
    assert _in_flight(pool) == []
    assert [r.request.seq for r in out] == list(range(5))
    assert [r.score for r in out] == [float(i) for i in range(5)]
    # a wait at each later launch (the second flush, the barrier's flush of
    # the queued order) and one at the barrier itself, none else
    assert pool.pool_stats["blocking_resolves"] == 3


def test_the_process_backend_resolves_within_the_same_pass():
    """A DeferredScore with no readiness check (the process backend's)
    reads as ready, so the pool resolves it at the end of the call that
    posted it: nothing is carried over, nothing waits."""
    pool, flight = _flight_pool(controlled=False)
    assert pool.submit(_order(0), 0.0) == []
    out = pool.submit(_order(1), 0.001)
    assert [r.request.seq for r in out] == [0, 1]
    assert _in_flight(pool) == [] and flight.waits == 1
    assert pool.pool_stats["deferred_flushes"] == 0
    assert pool.pool_stats["blocking_resolves"] == 0


def test_the_stage2_scorer_defers_the_read_and_scores_as_the_slot_path():
    """``Stage2Scorer`` returns once stage 2 is launched; the deferred
    scores equal, bit for bit, those of the path that finishes at once."""
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=8, feat_dim=4, mlp_dims=(8,))
    store = KVStore(dim=8)
    rng = np.random.default_rng(0)
    for e in range(6):
        store.put(pack_key(e, 0), rng.standard_normal(8).astype(np.float32))
    scorer = Stage2Scorer(lnn_init(jax.random.PRNGKey(1), cfg), cfg, store,
                          k_max=4)
    feats = rng.standard_normal((4, 4)).astype(np.float32)
    lists = [[(e, 0), (e + 1, 0)] for e in range(4)]
    d = scorer(feats, lists)
    assert isinstance(d, DeferredScore)
    probs, stale, version = d.wait()
    assert d.ready()
    emb, mask, st = store.lookup_batch_versioned(lists, 4)
    ref, ref_stale, ref_version = scorer.score_slots(feats, lists, emb, mask,
                                                     st)
    assert probs.dtype == np.float32 and probs.tobytes() == ref.tobytes()
    assert (stale == ref_stale).all() and version == ref_version == 0
    raw = scorer._score(scorer.params, 0, scorer._stage2, False, feats,
                        lists, emb, mask, st)
    assert isinstance(raw[0], ScoresInFlight)
    assert np.asarray(raw[0]).tobytes() == ref.tobytes()
