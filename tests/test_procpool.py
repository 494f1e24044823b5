"""``repro.stream.procpool`` — the true multi-process serving plane.

Four layers of coverage:

* **wire**: the pickle-free frame codec and the shared-memory ring
  allocator, driven in-process;
* **child**: the full :class:`ShardServer` command surface executed
  in-parent (the worker process is only a recv loop around ``handle``);
* **pool**: process lifecycle — heartbeat restart after a SIGKILL, journal
  shard restore, reshard, post-shutdown stats;
* **config/service**: the ``workers`` / ``admission.autoscale*`` knobs and
  the queue-depth autoscaler's hysteresis control law.

The headline bit-parity gates (process scores == inline scores for N=1/4,
including hot-swap, checkpoint/restore, and worker kill) live where their
inline twins live: ``test_stream.py`` (backend axis),
``test_checkpoint.py`` (backend axis), ``test_faultinject.py``
(worker_kill) — plus the engine-level hot-swap KV-byte gate below.
"""
import jax
import numpy as np
import pytest

from repro.core import LNNConfig, lnn_init
from repro.data import SynthConfig, generate_event_stream
from repro.serve.kvstore import pack_key
from repro.service import FraudService, ModelSection, ServiceConfig
from repro.stream import EngineConfig, StreamingEngine
from repro.stream.procpool import (
    ProcessWorkerPool,
    ShardServer,
    ShmRing,
    pack_frame,
    unpack_frame,
)
from repro.stream.workers import DepthAutoscaler
from repro.train.checkpoint import save_checkpoint


# ---------------------------------------------------------------- wire codec
def test_frame_roundtrip_multi_section():
    header = {"cmd": "score", "version": 3, "keys": [[1, 2], [3, 4]]}
    secs = [
        ("feats", np.arange(12, dtype="<f4").reshape(3, 4)),
        ("mask", np.asarray([1, 0, 1], np.int8)),
        ("empty", np.zeros((0, 4), np.float32)),
    ]
    buf = pack_frame(header, secs)
    h, out = unpack_frame(buf)
    assert h["cmd"] == "score" and h["version"] == 3
    assert h["keys"] == [[1, 2], [3, 4]]
    assert "sections" not in h          # descriptor list is consumed
    for name, arr in secs:
        assert out[name].dtype == arr.dtype
        assert out[name].shape == arr.shape
        assert out[name].tobytes() == arr.tobytes()
    # views are zero-copy and read-only — copy before mutating
    with pytest.raises(ValueError):
        out["feats"][0, 0] = 9.0


def test_frame_roundtrip_no_sections():
    h, out = unpack_frame(pack_frame({"cmd": "ping", "id": 7}))
    assert h == {"cmd": "ping", "id": 7} and out == {}


def test_shm_ring_alloc_free_wrap():
    ring = ShmRing(nbytes=64)
    try:
        a = ring.alloc(1, 24)
        b = ring.alloc(2, 24)
        assert (a, b) == (0, 24)
        assert ring.alloc(3, 24) is None          # full: 48 + 24 > 64
        ring.free(1)                              # tail advances to msg 2
        c = ring.alloc(3, 24)                     # wraps to offset 0
        assert c == 0
        arr = np.arange(6, dtype="<f4")
        ring.write(c, arr)
        assert bytes(ring.shm.buf[0:24]) == arr.tobytes()
        assert ring.alloc(4, 128) is None         # larger than capacity
    finally:
        ring.destroy()


# ------------------------------------------------- child server (in-parent)
@pytest.fixture(scope="module")
def server_world(tmp_path_factory):
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=8, feat_dim=4, mlp_dims=(8,))
    params = lnn_init(jax.random.PRNGKey(0), cfg)
    path = str(tmp_path_factory.mktemp("models") / "v0.npz")
    save_checkpoint(path, params)
    return cfg, params, path


def _server(cfg, path, num_shards=1):
    return ShardServer(
        wid=0, cfg=cfg,
        store_cfg=dict(dim=cfg.hidden_dim, num_shards=num_shards,
                       shard_by_entity=num_shards > 1),
        k_max=4, max_batch=4, model_path=path, model_version=0)


def _ask(srv, header, sections=None):
    """Drive one command; replies carry sections as (name, arr) pairs."""
    h, secs = srv.handle(header, sections or {})
    return h, dict(secs)


def test_shard_server_put_read_score_stats(server_world):
    cfg, params, path = server_world
    srv = _server(cfg, path)
    keys = np.asarray([pack_key(1, 0), pack_key(2, 0)], np.int64)
    vals = np.arange(16, dtype=np.float32).reshape(2, 8)
    h, _ = _ask(srv, {"cmd": "put", "id": 1, "pver": 0, "model_version": 0,
                      "stamp": 12.5},
                {"keys": keys, "values": vals})
    assert h["ok"] == 1 and h["n"] == 2

    h, s = _ask(srv, {"cmd": "read", "id": 2, "version": 0,
                      "pairs": [[1, 0], [9, 0]]})
    assert list(s["has"]) == [1, 0]
    assert s["emb"][0].tobytes() == vals[0].tobytes()

    feats = np.zeros((2, cfg.feat_dim), np.float32)
    h, s = _ask(srv, {"cmd": "score", "id": 3, "version": 0,
                      "keys": [[[1, 0]], [[2, 0]]], "remote": []},
                {"feats": feats})
    assert h["version"] == 0
    assert s["probs"].shape == (2,) and np.all((s["probs"] >= 0)
                                               & (s["probs"] <= 1))

    h, _ = _ask(srv, {"cmd": "stats", "id": 4})
    assert h["len"] == 2 and h["stats"]["puts"] == 2

    h, _ = _ask(srv, {"cmd": "ping", "id": 5})
    assert h["ok"] == 1 and h["wid"] == 0


def test_shard_server_score_merges_remote_slots(server_world):
    """Non-owned slots arrive pre-resolved; the server must splice them in
    at their (row, slot) positions instead of reading its own store."""
    cfg, params, path = server_world
    srv = _server(cfg, path)
    remote_emb = np.ones((2, cfg.hidden_dim), np.float32)
    feats = np.zeros((1, cfg.feat_dim), np.float32)
    h, s = _ask(
        srv,
        {"cmd": "score", "id": 1, "version": 0,
         "keys": [[[5, 0], [6, 0]]],
         # slot (0,0): remote hit with staleness 2; slot (0,1): remote miss
         "remote": [[0, 0, 1, 2], [0, 1, 0, -1]]},
        {"feats": feats, "remote_emb": remote_emb})
    assert h["ok"] == 1
    assert int(s["stale"][0]) == 2          # the remote hit's staleness won


def test_shard_server_snapshot_load_set_model(server_world, tmp_path):
    cfg, params, path = server_world
    srv = _server(cfg, path)
    keys = np.asarray([pack_key(3, 1)], np.int64)
    vals = np.full((1, 8), 2.0, np.float32)
    _ask(srv, {"cmd": "put", "id": 1, "pver": 1, "model_version": 0,
               "stamp": 1.0}, {"keys": keys, "values": vals})
    h, s = _ask(srv, {"cmd": "snapshot", "id": 2})
    assert h["shard_off"] == [0, 1] and h["len"] == 1
    assert s["keys"].tolist() == keys.tolist()
    assert s["versions"].tolist() == [1]

    # LOAD composes additively into a fresh server, shard by shard
    srv2 = _server(cfg, path)
    h2, _ = _ask(
        srv2,
        {"cmd": "load", "id": 3, "shard": 0},
        {"keys": s["keys"], "values": s["values"], "versions": s["versions"],
         "stamps": s["stamps"], "model_versions": s["model_versions"]})
    assert h2["ok"] == 1 and h2["n"] == 1
    _, r = _ask(srv2, {"cmd": "read", "id": 4, "version": 0,
                       "pairs": [[3, 1]]})
    assert list(r["has"]) == [1]

    # SET_MODEL registers a new version and scoring under it activates it
    p2 = lnn_init(jax.random.PRNGKey(1), cfg)
    path2 = str(tmp_path / "v1.npz")
    save_checkpoint(path2, p2)
    h, _ = _ask(srv, {"cmd": "set_model", "id": 5, "version": 1,
                      "path": path2})
    assert h["ok"] == 1
    h, _ = _ask(srv, {"cmd": "score", "id": 6, "version": 1,
                      "keys": [[]], "remote": []},
                {"feats": np.zeros((1, cfg.feat_dim), np.float32)})
    assert h["version"] == 1

    h, _ = _ask(srv, {"cmd": "warmup", "id": 7})
    assert h["ok"] == 1


def test_shard_server_errors_reply_not_raise(server_world):
    cfg, params, path = server_world
    srv = _server(cfg, path)
    h, secs = srv.handle({"cmd": "no_such", "id": 9}, {})
    assert "error" in h and "no_such" in h["error"] and secs == []
    h, _ = _ask(srv, {"cmd": "score", "id": 10, "version": 42,
                      "keys": [[]], "remote": []},
                {"feats": np.zeros((1, cfg.feat_dim), np.float32)})
    assert "error" in h            # unknown model version -> error frame


# --------------------------------------------------------- pool lifecycle
@pytest.fixture(scope="module")
def proc_world():
    events, g, _ = generate_event_stream(
        SynthConfig(num_users=40, num_rings=2, feature_noise=0.8, seed=5),
        rate_per_s=500.0)
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=16,
                    feat_dim=g.order_features.shape[1], mlp_dims=(8,))
    params = lnn_init(jax.random.PRNGKey(0), cfg)
    return events[:150], cfg, params


def _store_bytes(store):
    return {k: (np.asarray(v).tobytes(), ver, mv)
            for shard in store.shard_items()
            for k, v, ver, _st, mv in shard}


def test_processpool_requires_entity_affine_shards(proc_world):
    _events, cfg, params = proc_world
    with pytest.raises(ValueError, match="shard"):
        ProcessWorkerPool(
            params, cfg,
            dict(dim=cfg.hidden_dim, num_shards=1, shard_by_entity=False),
            num_workers=2)


def test_process_backend_refuses_to_build_on_a_tpu_host(proc_world,
                                                        monkeypatch):
    """The serving process holds the TPU; shard processes importing JAX
    could not get the chip, so the build refuses before any spawn."""
    _events, cfg, params = proc_world
    spawned = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ProcessWorkerPool, "_spawn_child",
                        lambda self, wid: spawned.append(wid))
    sc = ServiceConfig(model=ModelSection.from_lnn_config(cfg)).replace(
        workers={"backend": "process"})
    with pytest.raises(RuntimeError, match="holds the TPU"):
        FraudService(sc, params=params).build()
    assert spawned == []


def test_engine_rejects_injected_store_for_process_backend(proc_world):
    _events, cfg, params = proc_world
    from repro.serve.kvstore import KVStore

    with pytest.raises(ValueError, match="injected store|owns its KV"):
        StreamingEngine(params, cfg,
                        EngineConfig(backend="process"),
                        store=KVStore(cfg.hidden_dim))


def test_worker_death_heartbeat_restart_preserves_shard(proc_world):
    """SIGKILL a shard process between submissions: the next poll's
    liveness sweep must respawn it and restore its shard (snapshot journal
    + puts since) — KV bytes identical before and after, restart counted,
    and the stream finishes with every score delivered in order."""
    events, cfg, params = proc_world
    eng = StreamingEngine(params, cfg,
                          EngineConfig(max_batch=8, num_workers=2,
                                       backend="process"))
    try:
        eng.warmup()
        out = []
        for ev in events[:80]:
            out.extend(eng.submit(ev))
        pool = eng.pool
        before = _store_bytes(eng.store)
        assert len(before) > 0, "no KV writes before the kill — test is void"
        pool.kill_worker(0)
        assert pool.dead_workers() == 1
        out.extend(pool.poll(events[80].arrival))     # heartbeat sweep
        assert pool.dead_workers() == 0
        assert pool.ping() == [0, 1]
        assert _store_bytes(eng.store) == before, \
            "shard restore lost or corrupted KV state"
        for ev in events[80:]:
            out.extend(eng.submit(ev))
        out.extend(eng.flush())
        rows = pool.worker_summary()
        assert sum(r["restarts"] for r in rows) == 1
        assert all(r["alive"] for r in rows)
        seqs = [r.request.seq for r in out]
        assert seqs == sorted(seqs)
    finally:
        eng.close()


def test_process_reshard_preserves_store_and_scores(proc_world):
    """``reshard`` re-spawns the topology at a new width and re-places
    every entry under the new rendezvous layout — no entry lost, and the
    remaining stream still scores bit-identically to the inline oracle."""
    events, cfg, params = proc_world
    ref = StreamingEngine(params, cfg, EngineConfig(max_batch=8))
    s_ref = ref.replay(events).scores_by_order()

    eng = StreamingEngine(params, cfg,
                          EngineConfig(max_batch=8, num_workers=2,
                                       backend="process"))
    try:
        eng.warmup()
        out = []
        for ev in events[:70]:
            out.extend(eng.submit(ev))
        keys_before = set(_store_bytes(eng.store))
        out.extend(eng.pool.reshard(3))
        assert eng.pool.num_workers == 3
        assert len(eng.pool._children) == 3
        assert set(_store_bytes(eng.store)) == keys_before
        for ev in events[70:]:
            out.extend(eng.submit(ev))
        out.extend(eng.flush())
    finally:
        eng.close()
    s = {r.request.tag.order_id: r.score for r in out}
    # flush composition changes at the reshard boundary (forced drain), so
    # individual scores may batch differently — but every order scores, and
    # orders scored in untouched flushes stay bit-identical
    assert set(s) == set(s_ref)


def test_post_shutdown_summary_still_renders(proc_world):
    events, cfg, params = proc_world
    eng = StreamingEngine(params, cfg,
                          EngineConfig(max_batch=8, num_workers=2,
                                       backend="process"))
    rep = eng.replay(events[:40])
    n = len(eng.store)
    stats = dict(eng.store.stats)
    eng.close()
    eng.close()                                     # idempotent
    assert len(eng.store) == n                      # cached, not a dead call
    assert dict(eng.store.stats) == stats
    summary = rep.summary()
    assert all(not w["alive"] for w in summary["workers"])
    with pytest.raises(RuntimeError, match="shut down"):
        eng.pool.read_pairs(0, [[1, 0]], None)


# ------------------------------------------------ engine hot-swap KV parity
def test_process_hot_swap_parity_scores_and_kv_bytes(proc_world):
    """The tentpole gate, engine level: a mid-stream hot-swap replay under
    backend='process' (N=4) produces bit-identical scores AND bit-identical
    KV value bytes / versions / model-versions to the inline backend.
    (Stamps are wall-clock and excluded by construction.)"""
    events, cfg, params = proc_world
    params2 = lnn_init(jax.random.PRNGKey(1), cfg)
    half = len(events) // 2

    def run(backend):
        eng = StreamingEngine(
            params, cfg,
            EngineConfig(max_batch=8, num_workers=4, backend=backend))
        try:
            eng.warmup()
            out = []
            for i, ev in enumerate(events):
                if i == half:
                    eng.load_model(params2, 1)
                out.extend(eng.submit(ev))
            out.extend(eng.flush())
            traits = [(r.request.tag.order_id, r.score, r.staleness,
                       r.model_version, r.worker, r.batch_size) for r in out]
            return traits, _store_bytes(eng.store), dict(eng.store.stats)
        finally:
            eng.close()

    ti, kv_i, st_i = run("inline")
    tp, kv_p, st_p = run("process")
    assert ti == tp, "process scores diverged from inline"
    assert kv_i == kv_p, "process KV bytes diverged from inline"
    assert st_i == st_p, "store counters diverged from inline"


# ------------------------------------------------------------ config wiring
def test_workers_section_validation_and_roundtrip():
    sc = ServiceConfig(mode="streaming")
    assert sc.workers.backend == "inline"
    d = sc.to_dict()
    assert d["workers"]["backend"] == "inline"
    back = ServiceConfig.from_dict(d)
    assert back.workers.backend == "inline"

    proc = sc.replace(workers={"backend": "process", "ring_bytes": 8192})
    assert proc.workers.backend == "process"
    assert proc.to_engine_config().backend == "process"
    assert sc.to_engine_config().backend == "inline"

    with pytest.raises(ValueError):
        sc.replace(workers={"backend": "threads"})
    with pytest.raises(ValueError):
        sc.replace(workers={"ring_bytes": 16})
    with pytest.raises(ValueError, match="unknown"):
        sc.replace(workers={"backed": "process"})


def test_admission_autoscale_knob_validation():
    sc = ServiceConfig(mode="streaming")
    ok = sc.replace(admission={"autoscale": True, "autoscale_min_workers": 2,
                               "autoscale_max_workers": 4})
    assert ok.admission.autoscale and ok.admission.autoscale_max_workers == 4
    with pytest.raises(ValueError):
        sc.replace(admission={"autoscale_min_workers": 3,
                              "autoscale_max_workers": 2})
    with pytest.raises(ValueError):
        sc.replace(admission={"autoscale_low_depth": 9.0,
                              "autoscale_high_depth": 8.0})
    with pytest.raises(ValueError):
        sc.replace(admission={"autoscale_sustain": 0})
    with pytest.raises(ValueError):
        sc.replace(admission={"autoscale_cooldown": -1})


# -------------------------------------------------------- autoscaler control
class _FakePool:
    """Duck-typed pool: exactly the surface DepthAutoscaler touches."""

    def __init__(self, num_workers=2, max_batch=8):
        self.num_workers = num_workers
        self.max_batch = max_batch
        self.steal_threshold = None
        self.depth = 0
        self.resharded = []

    def __len__(self):
        return self.depth

    def reshard(self, n):
        self.resharded.append(n)
        self.num_workers = n
        return [f"drained@{n}"]


def test_autoscaler_hysteresis_scale_up_down_cooldown():
    pool = _FakePool(num_workers=1)
    a = DepthAutoscaler(pool, min_workers=1, max_workers=3, high_depth=4.0,
                        low_depth=1.0, sustain=3, cooldown=2)
    pool.depth = 20
    # sustain=3: two hot observations do nothing, the third scales up
    assert a.observe(0.0) == [] and a.observe(0.0) == []
    assert a.observe(0.0) == ["drained@2"]
    assert pool.num_workers == 2 and a.stats["scale_ups"] == 1
    # cooldown=2: the next two observations are ignored even though hot
    assert a.observe(0.0) == [] and a.observe(0.0) == []
    # still hot -> grows again after cooldown + sustain
    for _ in range(2):
        assert a.observe(0.0) == []
    assert a.observe(0.0) == ["drained@3"]
    assert pool.num_workers == 3
    # cold -> shrinks (after cooldown + sustain), floored at min_workers
    pool.depth = 0
    for _ in range(2 + 2):
        a.observe(0.0)
    assert a.observe(0.0) == ["drained@2"]
    assert a.stats["scale_downs"] == 1
    assert pool.resharded == [2, 3, 2]


def test_autoscaler_adaptive_steal_tracks_rolling_depth():
    pool = _FakePool(num_workers=2, max_batch=8)
    a = DepthAutoscaler(pool, autoscale=False, adaptive_steal=True,
                        high_depth=8.0, low_depth=1.0)
    pool.depth = 0
    a.observe(0.0)
    assert pool.steal_threshold == 8          # floored at max_batch
    pool.depth = 64
    for _ in range(DepthAutoscaler.WINDOW):
        a.observe(0.0)
    assert pool.steal_threshold == 64         # 2 * 64/2 once window saturates
    assert pool.resharded == []               # autoscale off: never reshards


def test_autoscaler_state_roundtrip():
    pool = _FakePool(num_workers=1)
    a = DepthAutoscaler(pool, sustain=5, cooldown=3)
    pool.depth = 30
    a.observe(0.0)
    a.observe(0.0)
    st = a.state_dict()
    b = DepthAutoscaler(_FakePool(num_workers=1), sustain=5, cooldown=3)
    b.load_state(st)
    assert b.state_dict() == st


@pytest.mark.parametrize("backend", ["inline", "process"])
def test_service_autoscale_end_to_end(proc_world, backend):
    """The admission knob wired through: sustained queue depth grows the
    pool via ``WorkerPool.reshard`` mid-stream, every admitted request
    still scores exactly once, and the scaling is visible in stats."""
    events, cfg, params = proc_world
    sc = ServiceConfig(
        mode="streaming", model=ModelSection.from_lnn_config(cfg),
    ).replace(
        engine={"num_workers": 1, "max_batch": 32, "max_wait_s": 1.0},
        store={"shard_by_entity": True},      # reshardable even from N=1
        workers={"backend": backend},
        admission={"autoscale": True, "adaptive_steal": True,
                   "autoscale_min_workers": 1, "autoscale_max_workers": 2,
                   "autoscale_high_depth": 3.0, "autoscale_low_depth": 0.5,
                   "autoscale_sustain": 2, "autoscale_cooldown": 0})
    svc = FraudService(sc, params=params).build()
    try:
        evs = events[:60]
        out = []
        for ev in evs:
            out.extend(svc.submit(ev))
        out.extend(svc.drain())
        st = svc.stats()
        assert st.extra["autoscaler"]["scale_ups"] >= 1
        assert svc.engine.pool.num_workers == 2
        assert svc.engine.pool.steal_threshold >= 32   # adaptive, floored
        admitted = [r for r in out if r.admitted]
        oids = sorted(r.request.tag.order_id for r in admitted)
        assert oids == sorted(ev.order_id for ev in evs)
        assert len(st.workers) == 2                    # tear-free snapshot
    finally:
        svc.close()
