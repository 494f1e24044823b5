"""Unit coverage for ``repro.stream.checkpoint``: the write-ahead log
(seqnos, CRC, torn-tail repair, compaction), the atomic checkpoint commit
protocol, the service-level lifecycle rules, and the gateway route.

The end-to-end bit-identity guarantee lives in ``test_faultinject.py`` —
this module pins the mechanisms that guarantee rests on.
"""
import json
import os

import jax
import numpy as np
import pytest

from repro.core import LNNConfig, lnn_init
from repro.data import SynthConfig, generate_event_stream
from repro.service import (FraudService, ModelSection, ServiceConfig,
                           ServiceLifecycleError)
from repro.stream.checkpoint import (CheckpointError, WriteAheadLog,
                                     decode_event, encode_event,
                                     latest_checkpoint, list_checkpoints,
                                     read_checkpoint, wal_path)
from repro.stream.events import CheckoutEvent
from repro.stream.microbatch import DeferredScore
from repro.utils import crashpoint
from repro.utils.crashpoint import SimulatedCrash


def _ev(i, snapshot=0, feats=(0.5, -0.25)):
    return CheckoutEvent(order_id=i, snapshot=snapshot,
                         entities=(i % 3, 10 + i % 2),
                         features=np.asarray(feats, np.float32),
                         label=float(i % 2), arrival=0.001 * i)


# ------------------------------------------------------------------ WAL core
def test_wal_append_scan_roundtrip(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    seqs = [wal.append_event("submit", _ev(i)) for i in range(5)]
    seqs.append(wal.append_model(1, "models/v1.npz"))
    seqs.append(wal.append_drain(0.125))
    assert seqs == list(range(1, 8))
    recs = list(wal.scan())
    assert [r["seq"] for r in recs] == seqs
    assert [r["kind"] for r in recs] == ["submit"] * 5 + ["model", "drain"]
    assert recs[5]["version"] == 1 and recs[5]["path"] == "models/v1.npz"
    assert recs[6]["now"] == 0.125
    # scan(after_seq) yields only the strict suffix
    assert [r["seq"] for r in wal.scan(after_seq=5)] == [6, 7]
    wal.close()


def test_event_codec_is_bit_exact():
    """Features survive the JSON trip bit-for-bit — including values that
    decimal round-tripping would corrupt (subnormals, -0.0, 1/3)."""
    feats = np.asarray([np.float32(1e-42), np.float32(-0.0),
                        np.float32(1.0) / np.float32(3.0),
                        np.float32(3.4e38)], np.float32)
    ev = CheckoutEvent(order_id=7, snapshot=3, entities=(2, 5, 9),
                      features=feats, label=1.0, arrival=0.75)
    back = decode_event(encode_event(ev))
    assert back.order_id == 7 and back.snapshot == 3
    assert back.entities == (2, 5, 9)
    assert back.features.tobytes() == feats.tobytes()
    assert back.label == 1.0 and back.arrival == 0.75


def test_wal_truncates_torn_tail(tmp_path):
    path = str(tmp_path / "wal.jsonl")
    wal = WriteAheadLog(path)
    for i in range(5):
        wal.append_event("submit", _ev(i))
    wal.close()
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"seq":6,"kind":"submit","order')   # the crash mid-write
    wal2 = WriteAheadLog(path)
    assert wal2.last_seq == 5
    assert len(list(wal2.scan())) == 5
    # the repaired log appends cleanly where the torn record would have been
    assert wal2.append_event("submit", _ev(5)) == 6
    assert [r["seq"] for r in wal2.scan()] == [1, 2, 3, 4, 5, 6]
    wal2.close()


def test_wal_rejects_interior_corruption(tmp_path):
    path = str(tmp_path / "wal.jsonl")
    wal = WriteAheadLog(path)
    for i in range(5):
        wal.append_event("submit", _ev(i))
    wal.close()
    lines = open(path, encoding="utf-8").read().splitlines()
    lines[2] = lines[2][:10] + "X" + lines[2][11:]   # flip a byte mid-log
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    with pytest.raises(CheckpointError, match="interior corruption"):
        WriteAheadLog(path)


def test_wal_crc_catches_field_tampering(tmp_path):
    """A syntactically valid line whose payload was edited fails its CRC —
    at the tail it is repaired away like any torn record."""
    path = str(tmp_path / "wal.jsonl")
    wal = WriteAheadLog(path)
    for i in range(3):
        wal.append_event("submit", _ev(i))
    wal.close()
    lines = open(path, encoding="utf-8").read().splitlines()
    rec = json.loads(lines[-1])
    rec["label"] = 1.0 - rec["label"]   # tamper, keep the stale crc
    lines[-1] = json.dumps(rec, separators=(",", ":"))
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    wal2 = WriteAheadLog(path)
    assert wal2.last_seq == 2
    wal2.close()


def test_wal_compaction_preserves_suffix(tmp_path):
    path = str(tmp_path / "wal.jsonl")
    wal = WriteAheadLog(path)
    for i in range(10):
        wal.append_event("submit", _ev(i))
    assert wal.compact(upto_seq=6) == 6
    assert wal.first_seq == 7 and wal.last_seq == 10
    assert [r["seq"] for r in wal.scan()] == [7, 8, 9, 10]
    # appends continue past compaction, and a reopen sees a coherent log
    assert wal.append_event("submit", _ev(10)) == 11
    wal.close()
    wal2 = WriteAheadLog(path)
    assert (wal2.first_seq, wal2.last_seq) == (7, 11)
    assert wal2.compact(upto_seq=3) == 0   # nothing to drop
    wal2.close()


def test_wal_rejects_unknown_event_kind(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    with pytest.raises(ValueError, match="unknown event record kind"):
        wal.append_event("mystery", _ev(0))
    wal.close()


# ------------------------------------------------------------- crash points
def test_crashpoint_arm_fire_disarm():
    crashpoint.arm("ingest.before", hit=2)
    crashpoint.fire("ingest.before")          # hit 1: survives
    crashpoint.fire("ingest.after")           # different point: ignored
    with pytest.raises(SimulatedCrash) as exc:
        crashpoint.fire("ingest.before")      # hit 2: dies
    assert exc.value.point == "ingest.before"
    # auto-disarmed before raising: recovery code can't re-trip it
    assert crashpoint.armed() is None
    crashpoint.fire("ingest.before")


def test_crashpoint_rejects_unknown_name():
    with pytest.raises(ValueError):
        crashpoint.arm("not.a.boundary")
    with pytest.raises(ValueError):
        crashpoint.arm("ingest.before", hit=0)
    crashpoint.disarm()


# ------------------------------------------------- service + checkpoint dirs
@pytest.fixture(scope="module")
def tiny_world():
    events, g, _ = generate_event_stream(
        SynthConfig(num_users=30, num_rings=2, feature_noise=0.8, seed=5),
        rate_per_s=500.0)
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=8,
                    feat_dim=g.order_features.shape[1], mlp_dims=(8,))
    params = lnn_init(jax.random.PRNGKey(0), cfg)
    return events[:24], cfg, params


def _build(cfg, params, mode="streaming"):
    sc = ServiceConfig(
        mode=mode, model=ModelSection.from_lnn_config(cfg),
    ).replace(engine={"num_workers": 1, "max_batch": 4})
    return FraudService(sc, params=params).build()


def test_enable_wal_lifecycle_rules(tiny_world, tmp_path):
    events, cfg, params = tiny_world
    svc = _build(cfg, params)
    with pytest.raises(ServiceLifecycleError, match="requires enable_wal"):
        svc.checkpoint()
    svc.enable_wal(str(tmp_path / "a"))
    with pytest.raises(ServiceLifecycleError, match="called twice"):
        svc.enable_wal(str(tmp_path / "b"))
    # a service that already saw traffic cannot start a log mid-history —
    # through the facade the state gate refuses; events smuggled past the
    # facade (direct engine access) trip the ingested-events gate
    late = _build(cfg, params)
    late.submit(events[0])
    with pytest.raises(ServiceLifecycleError, match="illegal in state"):
        late.enable_wal(str(tmp_path / "c"))
    smuggled = _build(cfg, params)
    smuggled.engine.ingest(events[0])
    with pytest.raises(ServiceLifecycleError, match="before any traffic"):
        smuggled.enable_wal(str(tmp_path / "c"))


def test_checkpoint_commit_is_atomic_and_idempotent(tiny_world, tmp_path):
    events, cfg, params = tiny_world
    root = str(tmp_path)
    svc = _build(cfg, params).enable_wal(root)
    for ev in events[:8]:
        svc.submit(ev)
    # a crash between state.npz and manifest.json leaves NO visible
    # checkpoint — only the .tmp staging dir, which the next writer cleans
    crashpoint.arm("checkpoint.mid")
    with pytest.raises(SimulatedCrash):
        svc.checkpoint()
    assert latest_checkpoint(root) is None
    staged = [d for d in os.listdir(os.path.join(root, "checkpoints"))
              if d.endswith(".tmp")]
    assert staged, "interrupted write should leave its staging dir"

    path = svc.checkpoint()
    assert latest_checkpoint(root) == path
    assert not any(d.endswith(".tmp")
                   for d in os.listdir(os.path.join(root, "checkpoints")))
    # same applied_seq -> same committed checkpoint, not a duplicate
    assert svc.checkpoint() == path
    manifest, arrays = read_checkpoint(path)
    assert manifest["applied_seq"] == svc.applied_seq
    assert manifest["events_logged"] == 8
    # malformed names / manifest-less dirs never shadow a real checkpoint
    os.makedirs(os.path.join(root, "checkpoints", "ckpt-garbage"))
    os.makedirs(os.path.join(root, "checkpoints", "ckpt-999999999999"))
    assert list_checkpoints(root) == [path]

    for ev in events[8:16]:
        svc.submit(ev)
    later = svc.checkpoint(compact=True)
    assert latest_checkpoint(root) == later
    # compaction dropped the covered prefix but kept the log coherent
    assert svc._wal.first_seq == svc.applied_seq + 1


def test_restore_without_checkpoint_replays_genesis(tiny_world, tmp_path):
    events, cfg, params = tiny_world
    root = str(tmp_path)
    svc = _build(cfg, params).enable_wal(root)
    for ev in events[:10]:
        svc.submit(ev)
    seen = svc.applied_seq
    svc2 = FraudService.restore(root)
    assert svc2.last_recovery["checkpoint"] is None
    assert svc2.last_recovery["replayed_records"] == seen
    assert svc2.applied_seq == seen
    assert svc2.engine.ingester.num_events == 10


def test_restore_keeps_logging_so_recoveries_chain(tiny_world, tmp_path):
    """crash -> restore -> crash -> restore composes: the restored service
    appends to the same WAL, so a second recovery sees the full history."""
    events, cfg, params = tiny_world
    root = str(tmp_path)
    svc = _build(cfg, params).enable_wal(root)
    for ev in events[:6]:
        svc.submit(ev)
    svc2 = FraudService.restore(root)
    for ev in events[6:12]:
        svc2.submit(ev)
    svc3 = FraudService.restore(root)
    assert svc3.engine.ingester.num_events == 12
    assert svc3.applied_seq == svc2.applied_seq
    # and the WAL on disk is one continuous validated history
    wal = WriteAheadLog(wal_path(root))
    assert wal.last_seq >= 12
    wal.close()


@pytest.mark.parametrize("backend", ["inline", "process"])
def test_checkpoint_restore_roundtrip_backends(tiny_world, tmp_path, backend):
    """Mid-stream checkpoint → abandon → restore → finish: merged scores
    and KV bytes equal an uninterrupted run, for BOTH worker backends.
    With backend='process' the checkpoint gathers shard state out of the
    worker processes and restore re-seeds a fresh set of them."""
    from faultinject import merge_responses, store_contents

    events, cfg, params = tiny_world

    def build():
        sc = ServiceConfig(
            mode="streaming", model=ModelSection.from_lnn_config(cfg),
        ).replace(engine={"num_workers": 2, "max_batch": 4},
                  workers={"backend": backend})
        return FraudService(sc, params=params).build()

    oracle = build()
    try:
        base = []
        for ev in events:
            base.extend(oracle.submit(ev))
        base.extend(oracle.drain())
        base_scores = merge_responses({}, base)
        base_store = store_contents(oracle.store)
    finally:
        oracle.close()

    root = str(tmp_path / "root")
    svc = build().enable_wal(root)
    delivered = []
    for ev in events[:12]:
        delivered.extend(svc.submit(ev))
    svc.checkpoint()
    for ev in events[12:16]:
        delivered.extend(svc.submit(ev))
    # abandon mid-stream (the crash): no flush, no drain — just release
    # the child processes and the WAL handle the restore will reopen
    svc.engine.pool.shutdown()
    svc._wal.close()

    svc2 = FraudService.restore(root)
    try:
        merged = merge_responses({}, delivered)
        merge_responses(merged, svc2.last_recovery["responses"])
        resume = svc2.engine.ingester.num_events
        assert resume == 16
        rest = []
        for ev in events[resume:]:
            rest.extend(svc2.submit(ev))
        rest.extend(svc2.drain())
        merge_responses(merged, rest)
        assert merged == base_scores, \
            f"{backend}: scores diverged across checkpoint/restore"
        assert store_contents(svc2.store) == base_store, \
            f"{backend}: KV bytes diverged across checkpoint/restore"
    finally:
        svc2.close()


def test_a_flush_in_flight_is_checkpointed_and_replayed(tiny_world,
                                                        tmp_path):
    """A checkpoint taken while a flush is in flight holds its results; a
    crash with a flush in flight (launched, scores not yet read back) loses
    nothing: restore + WAL replay reproduce every score of an
    uninterrupted run and answer every order."""
    from faultinject import merge_responses

    events, cfg, params = tiny_world
    oracle = _build(cfg, params)
    base = [r for ev in events for r in oracle.submit(ev)] + oracle.drain()
    base_scores = merge_responses({}, base)
    assert len(base_scores) == len(events)

    root = str(tmp_path)
    svc = _build(cfg, params).enable_wal(root)
    pool = svc.engine.pool
    for w in pool.workers:
        # never ready: a flush stays in flight until the next launch
        score = w.batcher.score_fn
        w.batcher.score_fn = lambda f, k, s=score: DeferredScore(
            s(f, k).wait, ready=lambda: False)
    delivered = []
    for ev in events[:10]:
        delivered.extend(svc.submit(ev))
    assert pool.workers[0].inflight is not None
    held = {r.seq for r in pool.workers[0].inflight.batch}
    path = svc.checkpoint()
    assert pool.workers[0].inflight is None
    manifest, arrays = read_checkpoint(path)
    saved = arrays["rq_seq"][arrays["rq_location"] == -1]
    assert held <= set(saved.tolist())
    crashpoint.arm("flush.in_flight")
    try:
        with pytest.raises(SimulatedCrash):
            for ev in events[10:]:
                delivered.extend(svc.submit(ev))
    finally:
        crashpoint.disarm()
    assert len(merge_responses({}, delivered)) < len(events)
    svc._wal.close()

    svc2 = FraudService.restore(root)
    merged = merge_responses({}, delivered)
    merge_responses(merged, svc2.last_recovery["responses"])
    rest = []
    for ev in events[svc2.engine.ingester.num_events:]:
        rest.extend(svc2.submit(ev))
    rest.extend(svc2.drain())
    merge_responses(merged, rest)
    assert merged == base_scores
    svc2.close()


def test_restore_rejects_future_format(tiny_world, tmp_path):
    events, cfg, params = tiny_world
    root = str(tmp_path)
    svc = _build(cfg, params).enable_wal(root)
    svc.submit(events[0])
    path = svc.checkpoint()
    mpath = os.path.join(path, "manifest.json")
    manifest = json.load(open(mpath))
    manifest["format"] = 999
    json.dump(manifest, open(mpath, "w"))
    with pytest.raises(CheckpointError, match="format"):
        FraudService.restore(root)


# ------------------------------------------------------------------- gateway
def test_gateway_checkpoint_route_and_boot(tiny_world, tmp_path):
    import urllib.error
    import urllib.request

    from repro.gateway import serve_gateway

    events, cfg, params = tiny_world
    root = str(tmp_path / "gw")
    sc = ServiceConfig(
        mode="streaming", model=ModelSection.from_lnn_config(cfg),
    ).replace(engine={"num_workers": 1, "max_batch": 4},
              gateway={"checkpoint_dir": root})

    def post(port, path, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    gw = serve_gateway(sc, params, warmup=False)
    try:
        assert gw.service.applied_seq == 0          # fresh boot enabled WAL
        for ev in events[:6]:
            post(gw.port, "/v1/score", {"event": {
                "order_id": ev.order_id, "snapshot": ev.snapshot,
                "entities": list(ev.entities),
                "features": ev.features.tolist(),
                "label": ev.label, "arrival": ev.arrival}})
        status, payload = post(gw.port, "/admin/checkpoint", {"compact": True})
        assert status == 200 and payload["compacted"]
        assert payload["applied_seq"] == 6
        assert latest_checkpoint(root) == payload["checkpoint"]
    finally:
        gw.close()   # service object abandoned: the simulated crash

    gw2 = serve_gateway(sc, None, warmup=False)     # reboot -> restore path
    try:
        svc = gw2.service
        assert svc.last_recovery is not None
        assert svc.engine.ingester.num_events == 6
    finally:
        gw2.close()

    # without a checkpoint_dir the route must refuse, not 500
    plain = serve_gateway(sc.replace(gateway={"checkpoint_dir": None}),
                          params, warmup=False)
    try:
        status, payload = post(plain.port, "/admin/checkpoint", {})
        assert status == 409 and "enable_wal" in payload["error"]
    finally:
        plain.close()
