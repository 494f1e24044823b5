"""Multi-worker sharded speed layer — ``repro.stream.workers``.

One micro-batch queue on one worker caps the speed layer at a single jit
dispatch stream; the serving tier, not the model, is the scaling bottleneck
(BRIGHT, arXiv 2205.13084).  This module shards that queue:

* :class:`ShardRouter` — key-affine routing: an event's primary entity maps
  to a worker by the SAME rendezvous hash the KV store uses for
  ``shard_by_entity`` placement (``serve.kvstore.entity_shard``, built on
  ``dist.sharding.rendezvous_shard``), so a request always lands on the
  worker that owns its entity's KV shard.  The worker count is fixed at
  construction and changes ONLY through an explicit :meth:`reshard` —
  never silently (property-tested).
* :class:`SpeedLayerWorker` — one shard's server: its own
  :class:`~repro.stream.microbatch.MicroBatcher` (independent size/deadline
  triggers) and its own :class:`Stage2Scorer` with a private jit cache
  (production workers are separate processes; private caches keep the
  simulation honest about per-worker warmup).
* :class:`WorkerPool` — fans submissions out through the router, pumps every
  worker's triggers on each virtual-clock advance, steals work from a
  backed-up shard into idle workers, and reassembles flushed scores in
  submission order through a reorder buffer.

Determinism: all queueing decisions run on the virtual clock (arrival
times), service occupancy is modeled by the configurable virtual
``service_model_s`` (0 = infinitely fast workers, the single-worker
default), and per-row scores are invariant to flush composition (pow2
buckets floored at 2 — see ``microbatch.bucket_size``).  Hence an N-worker
replay produces **bit-identical** scores to the single-worker engine for
any N and any flush interleaving (``tests/test_stream.py`` replay-parity).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core.hetero import type_code_of
from repro.core.lnn import LNNConfig, lnn_stage2_embed, lnn_stage2_online
from repro.models.hybrid import HybridModel
from repro.serve.kvstore import KVStore, entity_shard
from repro.stream.microbatch import (
    DeferredScore,
    MicroBatcher,
    PendingFlush,
    ScoredResult,
    ScoreRequest,
    bucket_size,
)
from repro.utils import crashpoint, spans


class ShardRouter:
    """Key-affine entity -> worker map (rendezvous placement).

    ``worker_of(entity) == KVStore(shard_by_entity=True).shard_of(key)``
    for every snapshot key of that entity, provided the store's
    ``num_shards`` equals the router's worker count — the pool constructs
    its store that way, so shard ownership and request routing agree by
    construction.

    The mapping is a pure function of (entity, num_workers): two routers
    with the same worker count agree on every entity, and the worker count
    is immutable except through :meth:`reshard` (which bumps ``epoch`` so
    observers can notice).  Growing N -> N+1 moves only ~1/(N+1) of the
    entities, all of them onto the new worker — the rendezvous minimal-
    movement property (property-tested in ``tests/test_workers.py``).
    """

    def __init__(self, num_workers: int):
        if num_workers < 1:
            raise ValueError("num_workers must be >= 1")
        self._num_workers = int(num_workers)
        self._epoch = 0

    @property
    def num_workers(self) -> int:
        return self._num_workers

    @property
    def epoch(self) -> int:
        """Bumped on every explicit reshard (observers cache against it)."""
        return self._epoch

    def worker_of(self, entity: int) -> int:
        return entity_shard(int(entity), self._num_workers)

    def route(self, entity_keys: list) -> int:
        """Worker for one request: the shard of its primary (first) entity
        key.  A request's other entities may live on other shards — their
        lookups are cross-shard reads, exactly like a remote KV fetch — but
        the *primary* entity's embedding is always shard-local.  Requests
        with no history (cold start, empty key list) carry no KV reads to
        co-locate; they pin to worker 0."""
        if not entity_keys:
            return 0
        return self.worker_of(entity_keys[0][0])

    def reshard(self, num_workers: int) -> int:
        """The ONLY way to change the worker count.  Returns the new epoch.

        On a live pool call :meth:`WorkerPool.reshard` instead — it drains
        the queues and migrates the worker list and the entity-affine KV
        shards together with the router (the pool guards against a router
        resharded out from under it)."""
        if num_workers < 1:
            raise ValueError("num_workers must be >= 1")
        self._num_workers = int(num_workers)
        self._epoch += 1
        return self._epoch


def slot_gaps(order_snapshots, entity_t_lists: list, stale: np.ndarray,
              k_max: int) -> np.ndarray:
    """Per-slot time gaps ``[B, k_max]`` int32 for HGT's relative temporal
    encoding: the order's snapshot minus the snapshot of the row served
    (``t_e`` less the slot's staleness), 0 on an empty slot (staleness
    -1)."""
    key_t = np.zeros((len(entity_t_lists), k_max), np.int32)
    for i, pairs in enumerate(entity_t_lists):
        for j, (_ent, t) in enumerate(pairs[:k_max]):
            key_t[i, j] = t
    dt = np.asarray(order_snapshots, np.int32)[:, None] - (key_t - stale)
    return np.where(stale >= 0, dt, 0).astype(np.int32)


def _sigmoid(logits: np.ndarray) -> np.ndarray:
    # host-side f64 sigmoid, NOT jax.nn.sigmoid: XLA CPU's vectorized
    # exp rounds differently per array length (bucket 2 vs 4 diverge by
    # 1 ulp), while numpy ufuncs are element-deterministic for any
    # shape — required for the bit-exact replay-parity guarantee
    return 1.0 / (1.0 + np.exp(-logits))


class ScoresInFlight:
    """A flush's probabilities while stage 2's output is on its way to the
    host.  The launch has started the device-to-host copy
    (``copy_to_host_async``).  :meth:`ready` asks without blocking whether
    the output is ready; :meth:`result` reads it (span ``s2.sync``) and
    runs the host head on it (``s2.tail``: the f64 sigmoid or the GBDT),
    once.  numpy reads the object as that result (``np.asarray``,
    ``copy()``)."""

    def __init__(self, out: jax.Array, dtype, head):
        self._out = out
        self._dtype = dtype
        self._head = head
        self._probs: np.ndarray | None = None

    def ready(self) -> bool:
        return self._probs is not None or self._out.is_ready()

    def result(self) -> np.ndarray:
        if self._probs is None:
            with spans.span("s2.sync"):
                x = np.asarray(self._out, self._dtype)
            with spans.span("s2.tail"):
                self._probs = self._head(x).astype(np.float32)
        return self._probs

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        return np.array(self.result(), dtype=dtype, copy=copy)

    def copy(self) -> np.ndarray:
        return self.result().copy()


class Stage2Scorer:
    """The speed-layer scoring callable for one worker: one versioned KV
    multi-get (snapshot fallback + staleness) and ONE jitted stage-2
    dispatch (the fused Pallas launch when ``cfg.use_pallas``).  Each
    worker owns its own instance, hence its own jit caches.

    A call does not wait for the device: it launches stage 2, starts the
    read-back, and returns a :class:`DeferredScore` whose ``wait()`` reads
    the output and runs the host head (:class:`ScoresInFlight`), so the
    host can prepare the next flush while this one's result travels back.
    The pool resolves it on a later call (:class:`WorkerPool`).

    The jit cache is **version-aware**: :meth:`set_model` registers a new
    parameter version under its own ``jax.jit`` wrapper, so swapping back
    to a previously-served version reuses its still-compiled cache, and a
    flush that already entered ``__call__`` finishes on the (params,
    version, jit) triple it captured at entry — in-flight micro-batches
    complete on the old model, the next flush scores on the new one.
    """

    def __init__(self, params, cfg: LNNConfig, store: KVStore, k_max: int,
                 model_version: int = 0):
        self.cfg = cfg
        self.store = store
        self.k_max = int(k_max)
        self._typed = bool(cfg.entity_types)
        # HGT encodes each slot's time gap: the micro-batcher hands this
        # scorer the orders' snapshots (``microbatch.FlushKeys``)
        self.reads_order_snapshot = cfg.gnn_type == "hgt"
        self._jits: dict[int, object] = {}
        self.set_model(params, model_version)

    def set_model(self, params, model_version: int) -> None:
        """Activate a parameter version.  New flushes score under it; the
        per-version jit wrapper keeps every version's compiled cache warm.

        ``params`` may be a plain ``lnn_init`` pytree (MLP risk head) or a
        :class:`~repro.models.hybrid.HybridModel` (GNN embedding -> GBDT):
        the hybrid's jit covers the fused embedding only, the booster runs
        on host like the MLP path's sigmoid."""
        version = int(model_version)
        hybrid = isinstance(params, HybridModel)
        if version not in self._jits:
            cfg = self.cfg
            stage2 = lnn_stage2_embed if hybrid else lnn_stage2_online
            if self.reads_order_snapshot:
                self._jits[version] = jax.jit(
                    lambda p, emb, mask, feats, st, dt: stage2(
                        p, cfg, emb, mask, feats, slot_type=st, slot_dt=dt)
                )
            elif hybrid:
                self._jits[version] = jax.jit(
                    lambda p, emb, mask, feats, st: lnn_stage2_embed(
                        p, cfg, emb, mask, feats, slot_type=st)
                )
            else:
                self._jits[version] = jax.jit(
                    lambda p, emb, mask, feats, st: lnn_stage2_online(
                        p, cfg, emb, mask, feats, slot_type=st)
                )
        # assign the tuple last-to-first so a concurrent flush reading
        # (params, version, jit) at entry never pairs new params with an
        # old version stamp
        self._hybrid = hybrid
        self._stage2 = self._jits[version]
        self.model_version = version
        self.params = params

    def _slot_types(self, entity_t_lists: list) -> np.ndarray:
        """Per-slot entity-type codes ``[B, k_max]`` (-1 = empty/untagged),
        aligned with the KV lookup's slot order (pair j -> slot j)."""
        st = np.full((len(entity_t_lists), self.k_max), -1, np.int32)
        for i, pairs in enumerate(entity_t_lists):
            for j, (ent, _t) in enumerate(pairs[: self.k_max]):
                st[i, j] = type_code_of(ent)
        return st

    def _slot_dt(self, entity_t_lists: list, stale: np.ndarray) -> np.ndarray:
        """:func:`slot_gaps` of a flush, under span ``s2.slot_dt``; the
        orders' snapshots ride on ``entity_t_lists`` as
        :class:`~repro.stream.microbatch.FlushKeys` (a flush with no served
        slot, such as a warm-up's, needs none)."""
        with spans.span("s2.slot_dt"):
            order_t = getattr(entity_t_lists, "order_snapshots", None)
            if order_t is None:
                if (stale >= 0).any():
                    raise ValueError("an hgt model scores slots only with the "
                                     "orders' snapshots (FlushKeys)")
                order_t = np.zeros(len(entity_t_lists), np.int32)
            return slot_gaps(order_t, entity_t_lists, stale, self.k_max)

    def __call__(self, feats: np.ndarray, entity_t_lists: list):
        """One flush: the KV multi-get, then stage 2 launched and its
        read-back started.  Returns a :class:`DeferredScore` whose
        ``wait()`` gives ``(probs, staleness, model_version)``."""
        # capture the active model ONCE per flush: an in-flight micro-batch
        # finishes on the version it started with even if set_model lands
        # mid-flush (async refresh thread / live hot-swap)
        params, version, stage2, hybrid = (
            self.params, self.model_version, self._stage2, self._hybrid)
        emb, mask, stale = self.store.lookup_batch_versioned(
            entity_t_lists, self.k_max, expected_model_version=version
        )
        probs, stale_max, version = self._score(
            params, version, stage2, hybrid, feats, entity_t_lists, emb,
            mask, stale)
        # a _score that is wrapped or replaced may hand back finished
        # probabilities, which are ready at once
        return DeferredScore(lambda: (np.asarray(probs), stale_max, version),
                             ready=getattr(probs, "ready", None))

    def score_slots(self, feats: np.ndarray, entity_t_lists: list,
                    emb: np.ndarray, mask: np.ndarray, stale: np.ndarray):
        """Score a batch whose KV slots were already resolved — the shard
        process path: the parent pre-reads cross-shard slots from their
        owners and the owner process fills its local slots, then calls
        this with the merged ``(emb, mask, stale)``.  Numerically identical
        to ``__call__`` by construction (same ``_score``), and finished
        before it returns."""
        params, version, stage2, hybrid = (
            self.params, self.model_version, self._stage2, self._hybrid)
        probs, stale_max, version = self._score(
            params, version, stage2, hybrid, feats, entity_t_lists, emb,
            mask, stale)
        return np.asarray(probs), stale_max, version

    def _score(self, params, version, stage2, hybrid, feats,
               entity_t_lists, emb, mask, stale):
        """Launch stage 2 and start its read-back, without waiting for it.
        Returns ``(probs, staleness, model_version)`` with ``probs`` a
        :class:`ScoresInFlight`.  Span ``s2.launch`` (``utils.spans``) is
        the jitted call, which returns once the launch is enqueued
        (dispatch and the arguments' transfer), and the start of the
        device-to-host copy; ``s2.sync`` and ``s2.tail`` run where the
        probabilities are read."""
        f = np.ascontiguousarray(feats, np.float32)
        st = self._slot_types(entity_t_lists) if self._typed else None
        if hybrid:
            # one jit dispatch for the fused embedding, booster on host —
            # numpy trees are element-deterministic, replay parity holds
            lnn, dtype, head = (params.lnn_params, np.float32,
                                params.gbdt.predict_proba)
        else:
            lnn, dtype, head = params, np.float64, _sigmoid
        if self.reads_order_snapshot:
            dt = self._slot_dt(entity_t_lists, stale)
            with spans.span("s2.launch"):
                out = stage2(lnn, emb, mask, f, st, dt)
                out.copy_to_host_async()
            return ScoresInFlight(out, dtype, head), stale.max(axis=1), version
        with spans.span("s2.launch"):
            out = stage2(lnn, emb, mask, f, st)
            out.copy_to_host_async()
        return ScoresInFlight(out, dtype, head), stale.max(axis=1), version

    def warmup(self, max_batch: int):
        """Compile every pow2 bucket shape this worker's batcher can emit."""
        buckets = sorted({bucket_size(n, max_batch)
                          for n in range(1, max_batch + 1)})
        for b in buckets:
            self(np.zeros((b, self.cfg.feat_dim), np.float32),
                 [[] for _ in range(b)]).wait()


class SpeedLayerWorker:
    """One shard of the speed layer: a private micro-batch queue with
    independent size/deadline flush triggers, a private jit cache, and a
    virtual single-server occupancy model.

    ``service_model_s`` is the *virtual* seconds one flush occupies the
    worker (0 = flushes are instantaneous, matching the single-worker
    engine).  While a flush's virtual service window is open the worker
    defers further flushes, its queue backs up past ``max_batch``, and the
    pool's work stealing can move the overflow to an idle worker — all on
    the virtual clock, so replays stay deterministic on any host.
    """

    def __init__(self, wid: int, scorer: Stage2Scorer,
                 max_batch: int = 16, max_wait_s: float = 0.005,
                 service_model_s: float = 0.0):
        self.wid = int(wid)
        self.scorer = scorer
        self.batcher = MicroBatcher(scorer, max_batch=max_batch,
                                    max_wait_s=max_wait_s)
        self.service_model_s = float(service_model_s)
        self.busy_until = 0.0
        # stamps never fall below this: stolen work reached this worker at
        # the steal time, so its recorded waits must not be backdated to
        # the victim's original (long-missed) triggers
        self.stamp_floor = 0.0
        # the flush launched last whose scores the pool has not taken yet
        self.inflight: PendingFlush | None = None
        self.stats = {"stolen_in": 0, "stolen_out": 0,
                      "max_queue_depth": 0, "depth_sum": 0,
                      "depth_samples": 0, "restarts": 0}

    def __len__(self) -> int:
        return len(self.batcher)

    def free(self, now: float) -> bool:
        return now >= self.busy_until

    def enqueue(self, req: ScoreRequest) -> None:
        self.batcher.enqueue(req)
        d = len(self.batcher)
        self.stats["max_queue_depth"] = max(self.stats["max_queue_depth"], d)

    def sample_depth(self) -> None:
        """Record queue depth for the bench's mean-depth counter."""
        self.stats["depth_sum"] += len(self.batcher)
        self.stats["depth_samples"] += 1

    def _flush_at(self, trigger: float, kind: str) -> list[ScoredResult]:
        """Serve one flush whose trigger fired at virtual time ``trigger``:
        the flush is stamped when the worker actually gets to it (the
        trigger, the end of the previous flush's service window, or the
        moment stolen work arrived — whichever is latest)."""
        stamp = max(trigger, self.busy_until, self.stamp_floor)
        out = self.batcher.flush(stamp, kind=kind.removesuffix("_flushes"))
        if out:
            self.batcher.stats[kind] += 1
            if isinstance(out, PendingFlush):
                # the scores are still on their way (the device's read-back
                # or this worker's shard process): the pool resolves it
                out.worker = self.wid
                out = [out]
            else:
                for r in out:
                    r.worker = self.wid
            if self.service_model_s > 0.0:
                self.busy_until = stamp + self.service_model_s
        return out

    def pump(self, now: float) -> list[ScoredResult]:
        """Run every flush whose trigger has fired and whose service window
        the worker can open by ``now`` — size triggers first (they fired
        earlier, when the queue filled), then the deadline trigger."""
        out: list[ScoredResult] = []
        while len(self.batcher) >= self.batcher.max_batch and self.free(now):
            trigger = self.batcher.nth_arrival(self.batcher.max_batch - 1)
            if trigger is None:      # raced away (steal) — queue re-checked
                break
            out.extend(self._flush_at(trigger, "size_flushes"))
        dl = self.batcher.deadline()
        if dl is not None and now >= dl and self.free(now):
            out.extend(self._flush_at(dl, "deadline_flushes"))
        return out

    def drain(self, now: float | None = None) -> list[ScoredResult]:
        """Force-flush everything queued (stream end).  Without an explicit
        ``now`` each residual batch is stamped at its own deadline — it
        would have flushed then anyway (timer semantics)."""
        out: list[ScoredResult] = []
        while len(self.batcher):
            dl = self.batcher.deadline()
            stamp = now if now is not None else (dl or 0.0)
            out.extend(self._flush_at(stamp, "deadline_flushes"))
        return out


class _ReorderBuffer:
    """Reassemble flushed results in submission (event) order.

    Workers flush independently, so scores surface out of order; the buffer
    holds them until the contiguous prefix of submission sequence numbers
    is complete — the result collector of the fan-out/fan-in topology."""

    def __init__(self):
        self._next = 0
        self._held: dict[int, ScoredResult] = {}
        self.max_held = 0

    def add(self, results: list[ScoredResult]) -> None:
        for r in results:
            self._held[r.request.seq] = r
        self.max_held = max(self.max_held, len(self._held))

    def release(self) -> list[ScoredResult]:
        out = []
        while self._next in self._held:
            out.append(self._held.pop(self._next))
            self._next += 1
        return out

    def __len__(self) -> int:
        return len(self._held)


class WorkerPool:
    """N key-affine speed-layer workers behind one submission interface.

    ``submit(request, now)`` routes by primary entity, pumps every worker's
    flush triggers at the new virtual time, runs the work-stealing pass,
    and returns whatever scored results completed *in submission order*
    (later results are held in the reorder buffer until their turn).

    Work stealing: when a shard's queue backs up past ``steal_threshold``
    requests (only possible when ``service_model_s`` > 0 keeps its worker
    busy), an idle worker with an empty queue takes the oldest half of the
    victim's queue and serves it — affinity is traded away only under
    pressure, and only explicitly (counted in ``stats["steals"]``).

    With ``num_workers=1`` the pool degenerates to exactly the single
    MicroBatcher engine: same triggers, same stamps, same scores.

    Flushes in flight: a flush whose scorer answers with a
    :class:`~repro.stream.microbatch.DeferredScore` stays in flight on its
    worker, one per worker.  At the start and the end of every ``poll`` and
    ``submit`` the pool resolves each in-flight flush that is ``ready()``,
    without waiting; a worker's next launch resolves its older flush after
    that launch, waiting if it must.  So ``submit`` returns the responses
    that are complete, which may include earlier orders' and not yet its
    own.  Every barrier resolves everything, waiting: ``flush`` (drain),
    ``reshard``, ``drain_to_depth`` and :meth:`settle` (checkpoint
    capture).  The process backend's deferred results are always ready, so
    it resolves within the pass that posted them.  ``pool_stats`` counts
    ``deferred_flushes`` (handed back on a later call than the one that
    launched them) and ``blocking_resolves`` (resolves that had to wait).
    """

    def __init__(self, params, cfg: LNNConfig, store: KVStore,
                 num_workers: int = 1, k_max: int = 8,
                 max_batch: int = 16, max_wait_s: float = 0.005,
                 service_model_s: float = 0.0,
                 steal_threshold: int | None = None):
        self.router = ShardRouter(num_workers)
        self.store = store
        self.max_batch = int(max_batch)
        self.steal_threshold = steal_threshold
        self.workers = [
            SpeedLayerWorker(
                w,
                Stage2Scorer(params, cfg, store, k_max),
                max_batch=max_batch,
                max_wait_s=max_wait_s,
                service_model_s=service_model_s,
            )
            for w in range(num_workers)
        ]
        self._reorder = _ReorderBuffer()
        self._seq = 0
        self.pool_stats = {"steals": 0, "stolen_requests": 0, "routed": 0,
                           "deferred_flushes": 0, "blocking_resolves": 0}

    @property
    def num_workers(self) -> int:
        return self.router.num_workers

    def __len__(self) -> int:
        return sum(len(w) for w in self.workers)

    def in_flight(self) -> int:
        """Orders whose flush is launched and not yet resolved."""
        return sum(w.inflight.n for w in self.workers
                   if w.inflight is not None)

    # ------------------------------------------------------------------ pump
    def _resolve(self, w: SpeedLayerWorker) -> None:
        """Hand worker ``w``'s in-flight flush to the reorder buffer,
        waiting for its scores if they are not there yet."""
        pf, w.inflight = w.inflight, None
        if not pf.ready():
            self.pool_stats["blocking_resolves"] += 1
        if pf.carried:
            self.pool_stats["deferred_flushes"] += 1
        self._reorder.add(pf.resolve())

    def _settle_ready(self, begin: bool = False) -> None:
        """Resolve every in-flight flush that is ready, without waiting.
        ``begin``: a new call starts, so what is still in flight is carried
        over to it."""
        for w in self.workers:
            if w.inflight is not None:
                w.inflight.carried |= begin
                if w.inflight.ready():
                    self._resolve(w)

    def settle(self) -> None:
        """Barrier: resolve every in-flight flush into the reorder buffer,
        waiting for the device.  The results are released by the next call;
        checkpoint capture calls this, so nothing is in flight when it reads
        the queues and the reorder buffer."""
        for w in self.workers:
            if w.inflight is not None:
                self._resolve(w)

    def _collect(self, results: list) -> None:
        """Take one pump pass's output: finished results go to the reorder
        buffer; a launched flush stays in flight on its worker, and the
        worker's older in-flight flush is resolved now, after the new
        launch, so the two overlap."""
        done: list[ScoredResult] = []
        for r in results:
            if isinstance(r, PendingFlush):
                crashpoint.fire("flush.in_flight")
                w = self.workers[r.worker]
                if w.inflight is not None:
                    self._resolve(w)
                w.inflight = r
            else:
                done.append(r)
        self._reorder.add(done)

    def poll(self, now: float) -> list[ScoredResult]:
        """Advance the virtual clock: fire every due trigger, then let idle
        workers steal from backed-up shards."""
        self._settle_ready(begin=True)
        results: list[ScoredResult] = []
        for w in self.workers:
            results.extend(w.pump(now))
        results.extend(self._steal_pass(now))
        self._collect(results)
        self._settle_ready()
        return self._reorder.release()

    def submit(self, request: ScoreRequest, now: float) -> list[ScoredResult]:
        """Route and enqueue one request, firing only the target worker's
        own triggers.  Callers advance the virtual clock with ``poll(now)``
        before submitting (the engine does exactly that), so other workers'
        due flushes have already fired — repeating the full sweep here
        would be a per-event no-op."""
        if self.router.num_workers != len(self.workers):
            raise RuntimeError(
                f"router has {self.router.num_workers} workers but the pool "
                f"has {len(self.workers)} — the router was resharded without "
                "the pool; use WorkerPool.reshard(n)"
            )
        self._settle_ready(begin=True)
        request.seq = self._seq
        self._seq += 1
        w = self.workers[self.router.route(request.entity_keys)]
        w.enqueue(request)
        self.pool_stats["routed"] += 1
        results = w.pump(now)
        for worker in self.workers:
            worker.sample_depth()
        self._collect(results)
        self._settle_ready()
        return self._reorder.release()

    def _steal_pass(self, now: float) -> list[ScoredResult]:
        if self.steal_threshold is None:
            return []
        out: list[ScoredResult] = []
        for thief in self.workers:
            if not thief.free(now) or len(thief) > 0:
                continue
            # deterministic victim choice: deepest queue, lowest wid wins ties
            victim = max(
                (w for w in self.workers if w is not thief),
                key=lambda w: (len(w), -w.wid),
                default=None,
            )
            if victim is None or len(victim) < self.steal_threshold:
                continue
            stolen = victim.batcher.take(len(victim) // 2)
            if not stolen:
                continue
            victim.stats["stolen_out"] += len(stolen)
            thief.stats["stolen_in"] += len(stolen)
            self.pool_stats["steals"] += 1
            self.pool_stats["stolen_requests"] += len(stolen)
            # the work only reached the thief now: flushes of it must not be
            # backdated to the victim's long-missed triggers
            thief.stamp_floor = max(thief.stamp_floor, now)
            for r in stolen:
                thief.enqueue(r)
            out.extend(thief.pump(now))
        return out

    # --------------------------------------------------------------- reshard
    def reshard(self, num_workers: int) -> list[ScoredResult]:
        """Atomically change the worker count on a live pool.

        Drains every queue first (returned in submission order — those
        scores were produced under the old topology), then moves the
        router, the entity-affine KV shards, and the worker list together,
        so the affinity contract ``worker_of(entity) == store.shard_of``
        holds before and after.  New workers start with fresh jit caches —
        a genuinely cold process, as in production."""
        out = self.flush()
        self.router.reshard(num_workers)
        if getattr(self.store, "shard_by_entity", False):
            self.store.reshard(num_workers)
        tmpl = self.workers[0]
        self.workers = [
            SpeedLayerWorker(
                w,
                Stage2Scorer(tmpl.scorer.params, tmpl.scorer.cfg,
                             self.store, tmpl.scorer.k_max,
                             model_version=tmpl.scorer.model_version),
                max_batch=tmpl.batcher.max_batch,
                max_wait_s=tmpl.batcher.max_wait_s,
                service_model_s=tmpl.service_model_s,
            )
            for w in range(num_workers)
        ]
        return out

    # ------------------------------------------------------------- hot-swap
    def set_model(self, params, model_version: int) -> None:
        """Activate a parameter version on every worker.  Flushes already
        executing finish on the version they captured at entry; every
        subsequent flush (on any worker) scores under the new one."""
        for w in self.workers:
            w.scorer.set_model(params, model_version)

    # ------------------------------------------------------------ admission
    def busy_workers(self, now: float) -> int:
        """Workers whose virtual service window is open at ``now`` — the
        admission controller's in-flight count."""
        return sum(1 for w in self.workers if not w.free(now))

    def force_flush_deepest(self, now: float) -> list[ScoredResult]:
        """Flush one batch off the deepest queue at virtual time ``now`` —
        the admission controller's block policy: the producer stalls while
        the most backed-up worker drains a batch.  Returns completed
        results in submission order (empty if every queue is empty)."""
        victim = max(self.workers, key=lambda w: (len(w), -w.wid))
        if len(victim) == 0:
            return []
        self._collect(victim._flush_at(now, "forced_flushes"))
        return self._reorder.release()

    def drain_to_depth(self, max_depth: int, now: float,
                       budget_s: float | None = None,
                       clock=time.monotonic) -> tuple[list[ScoredResult], bool]:
        """Bounded block-admission wait: force-flush the deepest queue until
        total depth drops below ``max_depth`` or the wall-clock ``budget_s``
        runs out.

        Returns ``(results, admitted)``.  ``admitted`` is False exactly when
        the stall timed out — the budget expired, or a flush pass freed no
        capacity (wedged queue) while a finite budget was set.  With
        ``budget_s=None`` the legacy semantics hold: a no-progress pass
        stops the stall and the caller admits over-cap (that unbounded/
        over-cap behavior is the bug ``admission.block_max_wait_s`` bounds —
        see ``tests/test_service.py::test_block_admission_bounded_wait``).
        """
        results: list[ScoredResult] = []
        deadline = None if budget_s is None else clock() + budget_s
        admitted = True
        while len(self) >= max_depth:
            if deadline is not None and clock() >= deadline:
                admitted = False
                break
            before = len(self)
            results.extend(self.force_flush_deepest(now))
            if len(self) >= before:
                # nothing freed (every queue empty, or the flush raced away):
                # legacy mode admits over-cap; a bounded stall sheds instead
                admitted = deadline is None
                break
        # a stall is a barrier: the producer gets every score it waited for
        self.settle()
        results.extend(self._reorder.release())
        return results, admitted

    # ----------------------------------------------------------------- drain
    def flush(self, now: float | None = None) -> list[ScoredResult]:
        """Drain every worker's queue (stream end), every in-flight flush
        and the reorder buffer."""
        self._settle_ready(begin=True)
        results: list[ScoredResult] = []
        for w in self.workers:
            results.extend(w.drain(now))
        self._collect(results)
        self.settle()
        out = self._reorder.release()
        assert len(self._reorder) == 0, "reorder buffer retained results"
        return out

    def warmup(self) -> None:
        for w in self.workers:
            w.scorer.warmup(w.batcher.max_batch)

    def shutdown(self) -> None:
        """Release backend resources.  The inline pool holds none; the
        process backend overrides this to stop its shard processes and
        unlink shared memory (``FraudService.close`` calls it)."""

    # ----------------------------------------------------------------- stats
    @property
    def stats(self) -> dict:
        """Aggregated MicroBatcher counters across workers (the single-
        worker engine's ``batcher.stats`` shape, so reports don't care
        how many workers ran) plus pool-level routing/steal counters."""
        agg: dict = {}
        for w in self.workers:
            for k, v in w.batcher.stats.items():
                agg[k] = agg.get(k, 0) + v
        agg.update(self.pool_stats)
        agg["reorder_max_held"] = self._reorder.max_held
        return agg

    def worker_summary(self) -> list[dict]:
        out = []
        for w in self.workers:
            s = w.batcher.stats
            mean_depth = (w.stats["depth_sum"] / w.stats["depth_samples"]
                          if w.stats["depth_samples"] else 0.0)
            out.append({
                "worker": w.wid,
                "requests": s["requests"],
                "flushes": s["flushes"],
                "size_flushes": s["size_flushes"],
                "deadline_flushes": s["deadline_flushes"],
                "stolen_in": w.stats["stolen_in"],
                "stolen_out": w.stats["stolen_out"],
                "max_queue_depth": w.stats["max_queue_depth"],
                "mean_queue_depth": mean_depth,
                "queue_depth": len(w),
                "restarts": w.stats.get("restarts", 0),
                "alive": True,
            })
        return out


class DepthAutoscaler:
    """Queue-depth-driven pool sizing + adaptive steal threshold.

    Observes total queued depth once per submission (virtual-clock
    telemetry, so replays are deterministic) and applies classic
    watermark-with-hysteresis control:

    * mean depth per worker above ``high_depth`` for ``sustain``
      consecutive observations -> grow by one worker
      (``WorkerPool.reshard``), up to ``max_workers``;
    * below ``low_depth`` for ``sustain`` observations -> shrink by one,
      down to ``min_workers``;
    * after any reshard, ``cooldown`` observations pass before another
      decision — reshard drains the queues, so depth right after a scale
      event says nothing about steady state.

    With ``adaptive_steal`` the pool's ``steal_threshold`` is re-derived
    each observation from a rolling depth window: twice the rolling mean
    depth per worker, floored at ``max_batch`` — backed-up shards shed
    work sooner under sustained pressure, and stealing quiets down when
    queues are shallow.  All state is plain counters + a bounded window,
    exposed via ``state_dict``/``load_state`` so checkpoints capture it
    and replay reproduces every scale decision bit-identically.
    """

    WINDOW = 32

    def __init__(self, pool: WorkerPool, *, min_workers: int = 1,
                 max_workers: int = 8, high_depth: float = 8.0,
                 low_depth: float = 1.0, sustain: int = 16,
                 cooldown: int = 64, autoscale: bool = True,
                 adaptive_steal: bool = False):
        if not 1 <= min_workers <= max_workers:
            raise ValueError("need 1 <= min_workers <= max_workers")
        if low_depth >= high_depth:
            raise ValueError("low_depth must be < high_depth")
        self.pool = pool
        self.min_workers = int(min_workers)
        self.max_workers = int(max_workers)
        self.high_depth = float(high_depth)
        self.low_depth = float(low_depth)
        self.sustain = max(1, int(sustain))
        self.cooldown = max(0, int(cooldown))
        self.autoscale = bool(autoscale)
        self.adaptive_steal = bool(adaptive_steal)
        self._above = 0
        self._below = 0
        self._cool = 0
        self._window: list[int] = []
        self.stats = {"scale_ups": 0, "scale_downs": 0, "observations": 0}

    def observe(self, now: float) -> list[ScoredResult]:
        """One control step.  Returns results drained by a reshard (they
        were scored under the old topology and must reach the caller)."""
        pool = self.pool
        depth = len(pool)
        n = pool.num_workers
        self.stats["observations"] += 1
        self._window.append(depth)
        if len(self._window) > self.WINDOW:
            self._window.pop(0)
        if self.adaptive_steal:
            mean = sum(self._window) / len(self._window)
            pool.steal_threshold = max(
                pool.max_batch, int(2.0 * mean / max(1, n)))
        if not self.autoscale:
            return []
        if self._cool > 0:
            self._cool -= 1
            return []
        per_worker = depth / max(1, n)
        self._above = self._above + 1 if per_worker > self.high_depth else 0
        self._below = self._below + 1 if per_worker < self.low_depth else 0
        target = n
        if self._above >= self.sustain and n < self.max_workers:
            target = n + 1
            self.stats["scale_ups"] += 1
        elif self._below >= self.sustain and n > self.min_workers:
            target = n - 1
            self.stats["scale_downs"] += 1
        if target == n:
            return []
        self._above = self._below = 0
        self._cool = self.cooldown
        return pool.reshard(target)

    # ----------------------------------------------------------- durability
    def state_dict(self) -> dict:
        """Control state for the checkpoint manifest — restoring it makes
        WAL-replayed traffic reproduce every scale decision exactly."""
        return {"above": self._above, "below": self._below,
                "cool": self._cool, "window": list(self._window),
                "stats": dict(self.stats)}

    def load_state(self, d: dict) -> None:
        self._above = int(d.get("above", 0))
        self._below = int(d.get("below", 0))
        self._cool = int(d.get("cool", 0))
        self._window = [int(x) for x in d.get("window", [])]
        self.stats.update(d.get("stats", {}))
