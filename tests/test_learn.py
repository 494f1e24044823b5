"""Unit coverage for the ``repro.learn`` plane's mechanisms: the WAL
training tap (receptive cones, delayed-label join, compaction pins), the
rolling-window policy and local optimizers, scheduled checkpointing with
retention, the shared rollback path, and the gateway's learn endpoints.

The promotion state machine and the end-to-end closed loop live in
``tests/test_learn_promotion.py``.
"""
import json
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from repro.core import LNNConfig, lnn_init
from repro.data import SynthConfig, generate_event_stream
from repro.learn import (LabelLog, RollingWindowTrainer, TrainingExample,
                         WalTrainingTap, WindowPolicy, adam, recall_at_budget,
                         sgd)
from repro.models.hybrid import HybridModel
from repro.service import (FraudService, ModelSection, ServiceConfig,
                           ServiceLifecycleError)
from repro.stream.checkpoint import (WriteAheadLog, list_checkpoints,
                                     prune_checkpoints)
from repro.stream.events import CheckoutEvent


def _ev(i, snapshot=0, entities=(1, 2), label=0.0, feats=None):
    f = np.asarray([0.5, -0.25] if feats is None else feats, np.float32)
    return CheckoutEvent(order_id=i, snapshot=snapshot,
                         entities=tuple(entities), features=f,
                         label=float(label), arrival=0.01 * i)


# ------------------------------------------------------------------ WAL tap
def test_tap_emits_examples_with_strictly_past_cones(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    wal.append_event("submit", _ev(0, snapshot=0, entities=(7, 8)))
    wal.append_event("submit", _ev(1, snapshot=1, entities=(7, 9)))
    wal.append_model(1, "models/v1.npz")     # non-event records are skipped
    wal.append_event("ingest", _ev(2, snapshot=2, entities=(8, 9)))
    with WalTrainingTap(wal, feat_dim=2) as tap:
        out = tap.poll()
        assert [ex.order_id for ex in out] == [0, 1, 2]
        assert [ex.seq for ex in out] == [1, 2, 4]
        # order 0 links only cold entities: its cone must be empty (the key
        # list is computed BEFORE add_order — no self-leak)
        assert out[0].entity_keys == ()
        # order 1 sees entity 7's snapshot-0 state, never its own snapshot
        assert out[1].entity_keys == ((7, 0),)
        assert out[2].entity_keys == ((8, 0), (9, 1))
        assert all(t < ex.snapshot
                   for ex in out for (_e, t) in ex.entity_keys)
        assert tap.cursor == wal.last_seq
        assert tap.stats["skipped"] == 1
        # idempotent: nothing new -> nothing emitted
        assert tap.poll() == []
    wal.close()


def test_tap_include_ingest_off(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    wal.append_event("submit", _ev(0))
    wal.append_event("ingest", _ev(1))
    with WalTrainingTap(wal, feat_dim=2, include_ingest=False) as tap:
        assert [ex.order_id for ex in tap.poll()] == [0]
        assert tap.stats["skipped"] == 1
    wal.close()


def test_label_log_join_overrides_event_label(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    for i in range(3):
        wal.append_event("submit", _ev(i, label=0.0))
    log = LabelLog()
    with WalTrainingTap(wal, feat_dim=2, label_log=log,
                        label_latency_s=10.0) as tap:
        assert tap.poll(now=0.1) == []          # window open, all pending
        assert tap.pending == 3
        log.record(1, 1.0)                      # chargeback lands for order 1
        out = tap.poll(now=0.1)                 # released early by the join
        assert [ex.order_id for ex in out] == [1]
        assert out[0].label == 1.0 and out[0].label_source == "label_log"
        out = tap.poll(now=100.0)               # the rest expire
        assert sorted(ex.order_id for ex in out) == [0, 2]
        assert all(ex.label == 0.0 and ex.label_source == "event"
                   for ex in out)
        assert tap.stats["label_joins"] == 1
        assert tap.stats["label_defaults"] == 2
        assert tap.pending == 0
    wal.close()


def test_tap_rejects_negative_latency(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    with pytest.raises(ValueError, match="label_latency_s"):
        WalTrainingTap(wal, feat_dim=2, label_latency_s=-1.0)
    wal.close()


# -------------------------------------------------- compaction-vs-reader race
def test_compact_respects_pins(tmp_path):
    """The WAL-compaction vs. training-tap race: a pin at the reader's
    cursor clamps ``compact()`` so unread records can never be deleted."""
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    for i in range(10):
        wal.append_event("submit", _ev(i))
    pin = wal.pin(3)                     # reader consumed seqs 1..3
    assert wal.min_pinned() == 3
    # a checkpoint wants to truncate through seq 10 — the pin clamps it
    wal.compact(10)
    assert [r["seq"] for r in wal.scan()] == [4, 5, 6, 7, 8, 9, 10]
    # the lagging reader can still consume its suffix
    assert len(list(wal.scan(after_seq=3))) == 7
    with pytest.raises(ValueError, match="only advance"):
        wal.move_pin(pin, 2)             # pins are monotonic
    wal.move_pin(pin, 8)
    wal.compact(10)
    assert [r["seq"] for r in wal.scan()] == [9, 10]
    wal.unpin(pin)
    wal.unpin(pin)                       # idempotent
    assert wal.min_pinned() is None
    wal.compact(10)
    assert list(wal.scan()) == []
    wal.close()


def test_tap_pins_survive_interleaved_compaction(tmp_path):
    """A tap that polls between compactions loses nothing: every submit
    record is emitted exactly once even when compaction runs concurrently
    behind its cursor."""
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    with WalTrainingTap(wal, feat_dim=2) as tap:
        seen = []
        for i in range(12):
            wal.append_event("submit", _ev(i))
            if i % 3 == 2:
                wal.compact(wal.last_seq)   # clamped at the tap's pin
                seen += [ex.order_id for ex in tap.poll()]
        seen += [ex.order_id for ex in tap.poll()]
        assert seen == list(range(12))
    wal.close()


# ----------------------------------------------------------- window + optim
def test_window_policy_validation():
    with pytest.raises(ValueError, match="min_window"):
        WindowPolicy(min_window=0)
    with pytest.raises(ValueError, match="max_window"):
        WindowPolicy(min_window=8, max_window=4)
    with pytest.raises(ValueError, match="stride"):
        WindowPolicy(stride=0)
    with pytest.raises(ValueError, match="stride"):
        WindowPolicy(max_window=64, stride=65)


@pytest.mark.parametrize("make", [sgd, adam])
def test_local_optimizers_descend_quadratic(make):
    """Both local optimizers minimize 0.5*||w||^2 (grad = w) — no optax."""
    init_fn, update_fn = make(0.1)
    params = {"w": np.asarray([4.0, -3.0], np.float32)}
    state = init_fn(params)
    norms = [float(np.linalg.norm(params["w"]))]
    for _ in range(50):
        grads = {"w": params["w"]}
        params, state = update_fn(grads, state, params)
        norms.append(float(np.linalg.norm(params["w"])))
    assert norms[-1] < 0.25 * norms[0]
    assert all(b <= a + 1e-6 for a, b in zip(norms, norms[1:]))


def test_trainer_rejects_bad_knobs():
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=4, feat_dim=2)
    with pytest.raises(ValueError, match="optimizer"):
        RollingWindowTrainer(cfg, optimizer="lbfgs")
    with pytest.raises(ValueError, match="head"):
        RollingWindowTrainer(cfg, head="transformer")
    with pytest.raises(ValueError, match="steps"):
        RollingWindowTrainer(cfg, steps=0)


def test_train_in_process_refuses_a_tpu_host(monkeypatch):
    """A spawned fine-tune child would import JAX beside the process that
    holds the TPU; the trainer refuses at construction instead."""
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=4, feat_dim=2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the TPU"):
        RollingWindowTrainer(cfg, in_process=True)
    assert RollingWindowTrainer(cfg).in_process is False


def _tap_ex(i, *, order_id=None, seq=None, label=0.0, snapshot=0):
    rng = np.random.default_rng(i)
    return TrainingExample(
        order_id=i if order_id is None else order_id, snapshot=snapshot,
        entities=(100 + i % 5, 200 + i % 3),
        features=rng.normal(0, 1, 6).astype(np.float32),
        label=label, arrival=0.01 * i, seq=i + 1 if seq is None else seq)


def test_trainer_ready_follows_stride():
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=4, feat_dim=6)
    tr = RollingWindowTrainer(
        cfg, WindowPolicy(min_window=4, max_window=8, stride=3), steps=1)
    for i in range(3):
        tr.add(_tap_ex(i))
    assert not tr.ready()                 # below min_window
    tr.add(_tap_ex(3))
    assert tr.ready()                     # first fire needs no stride
    tr.train(lnn_init(jax.random.PRNGKey(0), cfg))
    assert not tr.ready()                 # stride of fresh examples required
    tr.extend(_tap_ex(i) for i in range(4, 6))
    assert not tr.ready()
    tr.add(_tap_ex(6))
    assert tr.ready()


def test_trainer_window_dedup_keeps_latest():
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=4, feat_dim=6)
    tr = RollingWindowTrainer(cfg, WindowPolicy(min_window=1, max_window=8, stride=1))
    tr.add(_tap_ex(0, order_id=42, seq=1, label=0.0))
    tr.add(_tap_ex(1, order_id=7, seq=2))
    tr.add(_tap_ex(2, order_id=42, seq=3, label=1.0))   # label-log correction
    window = tr._window()
    assert len(window) == 2
    by_id = {e.order_id: e for e in window}
    assert by_id[42].label == 1.0 and by_id[42].seq == 3
    # live traffic (order_id == -1) is keyed by seq: never collapsed
    tr2 = RollingWindowTrainer(cfg, WindowPolicy(min_window=1, max_window=8, stride=1))
    tr2.add(_tap_ex(0, order_id=-1, seq=1))
    tr2.add(_tap_ex(1, order_id=-1, seq=2))
    assert len(tr2._window()) == 2


def test_trainer_finetunes_and_fits_hybrid_head():
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=4, feat_dim=6, mlp_dims=(4,))
    params = lnn_init(jax.random.PRNGKey(0), cfg)
    examples = [_tap_ex(i, label=float(i % 2), snapshot=i // 4)
                for i in range(12)]
    tr = RollingWindowTrainer(cfg, WindowPolicy(min_window=8, max_window=16, stride=8),
                              optimizer="adam", lr=5e-2, steps=6, head="mlp")
    tr.extend(examples)
    res = tr.train(params)
    assert res.window == 12 and len(res.losses) == 6
    assert all(np.isfinite(l) for l in res.losses)
    assert res.losses[-1] < res.losses[0]         # it actually descends
    assert res.model is res.params                # mlp head serves the pytree

    hy = RollingWindowTrainer(cfg, WindowPolicy(min_window=8, max_window=16, stride=8),
                              steps=2, head="hybrid", gbdt_trees=5, k_max=4)
    hy.extend(examples)
    hres = hy.train(params)
    assert isinstance(hres.model, HybridModel)
    assert hres.model.lnn_params is hres.params
    with pytest.raises(ValueError, match="empty window"):
        RollingWindowTrainer(cfg).train(params)


def test_recall_at_budget_skips_nan_labels():
    labels = [1.0, 0.0, float("nan"), 1.0, 0.0, 0.0]
    scores = [0.9, 0.1, 0.99, 0.8, 0.2, 0.3]
    # top-50% of the 5 labeled rows (k=2, stable) = scores 0.9, 0.8 -> both
    # positives captured
    assert recall_at_budget(labels, scores, 0.5) == 1.0
    assert np.isnan(recall_at_budget([0.0, 0.0], [0.5, 0.5], 0.5))
    assert np.isnan(recall_at_budget([], [], 0.5))


def test_learn_section_from_dict_roundtrip():
    sc = ServiceConfig.from_dict({
        "mode": "streaming",
        "model": {"num_gnn_layers": 1, "hidden_dim": 4, "feat_dim": 2},
        "learn": {"enabled": True, "min_window": 16, "stride": 8,
                  "head": "hybrid", "promote_margin": 0.05},
    })
    assert sc.learn.enabled and sc.learn.min_window == 16
    assert sc.learn.head == "hybrid"
    back = ServiceConfig.from_dict(sc.to_dict())
    assert back.learn == sc.learn


# ------------------------------------------- scheduled checkpoint + retention
@pytest.fixture(scope="module")
def learn_world():
    events, g, _ = generate_event_stream(
        SynthConfig(num_users=30, num_rings=2, feature_noise=0.8, seed=5),
        rate_per_s=500.0)
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=8,
                    feat_dim=g.order_features.shape[1], mlp_dims=(8,))
    params = lnn_init(jax.random.PRNGKey(0), cfg)
    return events[:24], cfg, params


def _build(cfg, params):
    sc = ServiceConfig(
        mode="streaming", model=ModelSection.from_lnn_config(cfg),
    ).replace(engine={"num_workers": 1, "max_batch": 4})
    return FraudService(sc, params=params).build()


def test_auto_checkpoint_lifecycle_rules(learn_world, tmp_path):
    _events, cfg, params = learn_world
    svc = _build(cfg, params)
    with pytest.raises(ServiceLifecycleError, match="requires enable_wal"):
        svc.enable_auto_checkpoint(every_s=1.0)
    svc.enable_wal(str(tmp_path / "wal"))
    with pytest.raises(ServiceLifecycleError, match="every_s and/or"):
        svc.enable_auto_checkpoint()
    with pytest.raises(ValueError, match="every_s"):
        svc.enable_auto_checkpoint(every_s=0.0)
    with pytest.raises(ValueError, match="every_windows"):
        svc.enable_auto_checkpoint(every_windows=0)
    with pytest.raises(ValueError, match="keep_last"):
        svc.enable_auto_checkpoint(every_s=1.0, keep_last=0)
    svc.close()


def test_auto_checkpoint_fires_on_injected_clock(learn_world, tmp_path):
    events, cfg, params = learn_world
    root = str(tmp_path / "wal")
    svc = _build(cfg, params).enable_wal(root)
    t = {"now": 0.0}
    svc.enable_auto_checkpoint(every_s=10.0, keep_last=2,
                               clock=lambda: t["now"])
    for ev in events[:4]:
        svc.submit(ev)
    assert svc.stats().extra["auto_checkpoint"]["checkpoints"] == 0
    t["now"] = 11.0                       # cadence due on the next apply
    svc.submit(events[4])
    st = svc.stats().extra["auto_checkpoint"]
    assert st["checkpoints"] == 1
    assert len(list_checkpoints(root)) == 1
    # each subsequent period adds one, retention keeps the newest 2
    for i, ev in enumerate(events[5:9]):
        t["now"] += 11.0
        svc.submit(ev)
    st = svc.stats().extra["auto_checkpoint"]
    assert st["checkpoints"] == 5
    assert len(list_checkpoints(root)) == 2
    assert st["pruned"] == 3
    svc.close()


def test_prune_checkpoints_keeps_newest(learn_world, tmp_path):
    events, cfg, params = learn_world
    root = str(tmp_path / "wal")
    svc = _build(cfg, params).enable_wal(root)
    for i, ev in enumerate(events[:6]):
        svc.submit(ev)
        if i % 2 == 1:
            svc.checkpoint()
    found = list_checkpoints(root)
    assert len(found) == 3
    removed = prune_checkpoints(root, keep_last=2)
    assert removed == found[:1]
    assert list_checkpoints(root) == found[1:]
    assert prune_checkpoints(root, keep_last=2) == []
    with pytest.raises(ValueError, match="keep_last"):
        prune_checkpoints(root, keep_last=0)
    svc.close()


# -------------------------------------------------------- shared rollback path
def test_rollback_model_restores_last_good(learn_world):
    _events, cfg, params = learn_world
    svc = _build(cfg, params)
    with pytest.raises(ServiceLifecycleError, match="last-good"):
        svc.rollback_model()              # no swap has happened yet
    v1 = svc.register_perturbed(0, scale=0.0, version=1)
    svc.activate_model(v1)
    assert svc.last_good_version == 0
    svc.enable_shadow(0, fraction=1.0)    # rollback also kills the alert src
    restored = svc.rollback_model("test reason")
    assert restored == 0 and svc.model_version == 0
    assert svc.shadow_stats() == {}
    st = svc.stats()
    assert st.rollbacks == 1 and st.last_good_version is None
    assert svc.last_rollback == {"from": v1, "to": 0, "reason": "test reason"}
    with pytest.raises(ServiceLifecycleError):
        svc.rollback_model()              # consumed: no ping-pong
    svc.close()


def test_register_perturbed_keeps_hybrid_structure(learn_world):
    """Perturbing a hybrid version must stay a HybridModel (tree_map over
    the dataclass would collapse it into a 0-d object array and crash the
    speed layer's non-hybrid scoring branch)."""
    _events, cfg, params = learn_world
    svc = _build(cfg, params)
    rng = np.random.default_rng(0)
    emb_dim = cfg.hidden_dim + cfg.feat_dim
    from repro.baselines.gbdt import GBDTConfig
    from repro.models.hybrid import train_hybrid

    hy = train_hybrid(params, cfg,
                      rng.normal(0, 1, (32, emb_dim)).astype(np.float32),
                      (rng.random(32) > 0.7).astype(np.float32),
                      gbdt_cfg=GBDTConfig(num_trees=3))
    vh = svc.register_model(hy)
    vp = svc.register_perturbed(vh, scale=2.0)
    perturbed = svc.model_params(vp)
    assert isinstance(perturbed, HybridModel)
    assert perturbed.gbdt is hy.gbdt      # head shared by reference
    a = np.asarray(jax.tree_util.tree_leaves(hy.lnn_params)[0])
    b = np.asarray(jax.tree_util.tree_leaves(perturbed.lnn_params)[0])
    assert not np.allclose(a, b)
    svc.close()


# ------------------------------------------------------------ gateway surface
def _post(url, body):
    req = urllib.request.Request(url, data=json.dumps(body).encode(),
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def test_gateway_learn_endpoints(learn_world, tmp_path):
    from repro.gateway import serve_gateway

    events, cfg, params = learn_world
    sc = ServiceConfig(
        mode="streaming", model=ModelSection.from_lnn_config(cfg),
    ).replace(engine={"num_workers": 1, "max_batch": 4},
              gateway={"checkpoint_dir": str(tmp_path / "wal")},
              learn={"enabled": True, "min_window": 4, "stride": 4,
                     "steps": 1, "min_eval": 2, "min_eval_pos": 1,
                     "eval_max": 8})
    gw = serve_gateway(sc, params)
    try:
        for ev in events[:6]:
            _post(gw.url + "/v1/score", {"event": {
                "order_id": ev.order_id, "snapshot": ev.snapshot,
                "entities": list(ev.entities),
                "features": ev.features.tolist(), "label": float(ev.label),
                "arrival": ev.arrival}})
        code, out = _post(gw.url + "/admin/train", {"force": True})
        assert code == 200
        assert out["trained"] is not None and out["examples"] >= 1
        assert out["state"] == "shadowing"
        code, body = _get(gw.url + "/v1/learn/stats")
        stats = json.loads(body)
        assert code == 200 and stats["state"] == "shadowing"
        assert stats["trainer"]["fires"] == 1
        _code, metrics = _get(gw.url + "/metrics")
        assert 'repro_learn_info{state="shadowing"} 1' in metrics
        assert "repro_learn_fires_total 1" in metrics
        assert "repro_service_rollbacks_total 0" in metrics
    finally:
        gw.close()


def test_gateway_learn_endpoints_409_without_learner(learn_world):
    from repro.gateway import FraudGateway

    _events, cfg, params = learn_world
    svc = _build(cfg, params)
    gw = FraudGateway(svc).start()
    try:
        code, out = _post(gw.url + "/admin/train", {})
        assert code == 409 and "learn.enabled" in out["error"]
        code, body = _get(gw.url + "/v1/learn/stats")
        assert code == 409
    finally:
        gw.close()


def test_gateway_auto_rollback_ignores_candidate_shadows(learn_world):
    """gateway.auto_rollback fires only for 'canary'-role shadows: a learn
    candidate is EXPECTED to diverge, so its alert must not roll back."""
    from repro.gateway import FraudGateway

    events, cfg, params = learn_world
    sc = ServiceConfig(
        mode="streaming", model=ModelSection.from_lnn_config(cfg),
    ).replace(engine={"num_workers": 1, "max_batch": 4},
              gateway={"auto_rollback": True})
    svc = FraudService(sc, params=params).build()
    v1 = svc.register_perturbed(0, scale=0.0, version=1)
    svc.activate_model(v1)                # last_good = 0, armed
    vc = svc.register_perturbed(v1, scale=5.0)
    svc.enable_shadow(vc, fraction=1.0, threshold=1e-6, collect_eval=8,
                      role="candidate")
    gw = FraudGateway(svc, config=sc.gateway).start()
    try:
        for ev in events[:8]:
            _post(gw.url + "/v1/score", {"event": {
                "order_id": 50_000 + ev.order_id, "snapshot": ev.snapshot,
                "entities": list(ev.entities),
                "features": ev.features.tolist(), "arrival": ev.arrival}})
        _post(gw.url + "/admin/drain", {})
        assert svc.shadow_stats().get("alert_active") is True
        assert svc.stats().rollbacks == 0          # candidate: no rollback
        assert svc.model_version == v1
    finally:
        gw.close()


def test_gateway_auto_rollback_on_canary_alert(learn_world):
    from repro.gateway import FraudGateway

    events, cfg, params = learn_world
    sc = ServiceConfig(
        mode="streaming", model=ModelSection.from_lnn_config(cfg),
    ).replace(engine={"num_workers": 1, "max_batch": 4},
              gateway={"auto_rollback": True})
    svc = FraudService(sc, params=params).build()
    bad = svc.register_perturbed(0, scale=5.0)
    svc.activate_model(bad)               # last_good = 0
    svc.enable_shadow(0, fraction=1.0, threshold=1e-6)   # role defaults canary
    gw = FraudGateway(svc, config=sc.gateway).start()
    try:
        for ev in events[:8]:
            _post(gw.url + "/v1/score", {"event": {
                "order_id": 60_000 + ev.order_id, "snapshot": ev.snapshot,
                "entities": list(ev.entities),
                "features": ev.features.tolist(), "arrival": ev.arrival}})
        _post(gw.url + "/admin/drain", {})
        assert svc.model_version == 0              # rolled back to last-good
        assert svc.stats().rollbacks == 1
        assert "auto-rollback" in svc.last_rollback["reason"]
        _code, metrics = _get(gw.url + "/metrics")
        assert "repro_service_rollbacks_total 1" in metrics
    finally:
        gw.close()
