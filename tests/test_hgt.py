"""HGT as the LNN's GNN (``gnn_type="hgt"``) against the plain numpy
reference the on-chip benchmark decides ``correct`` with
(``benchmarks/onchip/configs/lnn_hgt_reference.py``), at a small size on
the CPU: H=32, 4 heads, F=12, 3 layers, over a ``data/synth.py`` stream.
Pallas kernels run in interpret mode."""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    LNNConfig,
    lnn_forward,
    lnn_init,
    lnn_order_tower,
    lnn_stage1,
    lnn_stage2_batch,
    lnn_stage2_online,
)
from repro.core.dds import IncrementalDDSBuilder
from repro.core.graph import pad_graph
from repro.core.layers import RTE_TABLE, _block_diag

ROOT = Path(__file__).resolve().parents[1]
CONFIGS = ROOT / "benchmarks" / "onchip" / "configs"
H, HEADS, F, L = 32, 4, 12, 3
MAX_HISTORY, MAX_DEG, K_MAX = 8, 32, 8
SEED = 1234
#: the limits ``correct`` holds the lnn-hgt configuration to on the chip
LIMITS = json.loads((CONFIGS / "lnn-hgt.json").read_text())["limits"]
CFG = LNNConfig(gnn_type="hgt", num_gnn_layers=L, hidden_dim=H,
                num_heads=HEADS, mlp_dims=(16, 8), feat_dim=F)
MODEL = {"gnn_type": "hgt", "num_gnn_layers": L, "hidden_dim": H,
         "num_heads": HEADS, "mlp_dims": [16, 8], "feat_dim": F}


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "test_hgt_reference", CONFIGS / "lnn_hgt_reference.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def events():
    from repro.data import SynthConfig
    from repro.data.synth import generate_event_stream

    ev, _, _ = generate_event_stream(
        SynthConfig(num_users=40, num_rings=2, num_snapshots=10, seed=3))
    return ev


def _orders(events, stretch=1):
    snap = np.asarray([e.snapshot for e in events], np.int64) * stretch
    ents = np.asarray([e.entities for e in events], np.int64)
    feats = np.stack([e.features for e in events]).astype(np.float32)
    return snap, ents, feats


def _program_graph(snap, ents, feats):
    b = IncrementalDDSBuilder(F, max_history=MAX_HISTORY)
    for i in range(len(snap)):
        b.add_order(ents[i].tolist(), int(snap[i]), feats[i])
    dds = b.build()
    n = 64
    while n < dds.coo.num_nodes:
        n *= 2
    return dds, pad_graph(dds.coo, num_nodes=n, max_deg=MAX_DEG)


def _entity_rows(dds, h, ref_graph, h_ref):
    """Program and reference stage-1 rows of every (entity, t) pair."""
    pairs = ref_graph.pairs
    nid = np.asarray([dds.entity_snap_ids[(int(e), int(t))] for e, t in pairs])
    return np.asarray(h)[nid], h_ref[ref_graph.row_of(pairs[:, 0], pairs[:, 1])]


def _gap(a, b):
    return float(np.abs(np.asarray(a) - b).max() / np.abs(b).max())


def _stage1_gap(ref, params, snap, ents, feats, cfg=CFG, ref_params=None,
                precision="highest"):
    dds, pg = _program_graph(snap, ents, feats)
    g = ref.Stage1Graph(snap, ents, feats, max_history=MAX_HISTORY,
                        max_deg=MAX_DEG)
    ref_params = ref_params if ref_params is not None else jax.device_get(params)
    h_ref = ref.stage1(ref_params, "hgt", g, precision)
    h = lnn_stage1(params, cfg, pg)
    return _gap(*_entity_rows(dds, h, g, h_ref))


def test_reference_draws_the_programs_weights(ref):
    # jitted, as the benchmark draws the program's weights
    prog = jax.tree.leaves(jax.jit(lambda k: lnn_init(k, CFG))(
        jax.random.PRNGKey(SEED)))
    mine = jax.tree.leaves(ref.init_params(SEED, MODEL))
    assert len(prog) == len(mine)
    for a, b in zip(prog, mine):
        np.testing.assert_array_equal(np.asarray(a), b)


def test_hgt_stage1_matches_the_reference(ref, events):
    params = lnn_init(jax.random.PRNGKey(SEED), CFG)
    assert _stage1_gap(ref, params, *_orders(events)) < LIMITS["write_gap"]


@pytest.mark.parametrize("stretch", [1, 40], ids=["in-table", "past-table"])
def test_stage1_kernels_match_the_jnp_path(ref, events, stretch):
    """Interpret-mode Pallas path against the jnp path; stretched
    snapshots put time gaps past ``RTE_TABLE``, which must be computed
    exactly (the per-edge path), not clipped to the table."""
    snap, ents, feats = _orders(events, stretch)
    assert (stretch == 1) == (np.ptp(snap) < RTE_TABLE)
    params = lnn_init(jax.random.PRNGKey(SEED), CFG)
    _, pg = _program_graph(snap, ents, feats)
    h = np.asarray(lnn_stage1(params, CFG, pg))
    hp = np.asarray(lnn_stage1(params, dataclasses.replace(CFG, use_pallas=True),
                               pg))
    assert _gap(hp, h) < 1e-5
    if stretch > 1:
        assert _stage1_gap(ref, params, snap, ents, feats,
                           cfg=dataclasses.replace(CFG, use_pallas=True)) \
            < LIMITS["write_gap"]


@pytest.fixture(scope="module")
def community(small_communities):
    feat_dim = small_communities[0].graph.features.shape[1]
    cfg = dataclasses.replace(CFG, feat_dim=feat_dim)
    return cfg, lnn_init(jax.random.PRNGKey(0), cfg), small_communities[0]


def test_forward_is_stage2_of_stage1(community):
    cfg, params, b = community
    h = lnn_stage1(params, cfg, b.graph)
    np.testing.assert_allclose(
        np.asarray(lnn_forward(params, cfg, b.graph)),
        np.asarray(lnn_stage2_batch(params, cfg, h, b.graph)), atol=1e-6)


def test_order_tower_equals_the_stage1_rows_of_orders(community):
    cfg, params, b = community
    n_orders = b.global_order_ids.size
    tower = lnn_order_tower(params, cfg, b.graph.features[:n_orders])
    for c in (cfg, dataclasses.replace(cfg, use_pallas=True)):
        h = lnn_stage1(params, c, b.graph)
        np.testing.assert_allclose(np.asarray(tower),
                                   np.asarray(h[:n_orders]), atol=1e-6)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "kernel"])
def test_online_stage2_matches_the_batch_path(community, use_pallas):
    """The speed layer over KV rows with per-slot gaps equals the batch
    path's final hop over the DDS graph's ENTITY_TO_ORDER edges."""
    cfg, params, b = community
    n_orders = b.global_order_ids.size
    h = np.asarray(lnn_stage1(params, cfg, b.graph))
    full = np.asarray(lnn_stage2_batch(params, cfg, jnp.asarray(h), b.graph))
    snap = np.asarray(b.graph.snapshot)
    emb = np.zeros((n_orders, K_MAX, H), np.float32)
    msk = np.zeros((n_orders, K_MAX), np.float32)
    dt = np.zeros((n_orders, K_MAX), np.int32)
    for o, hops in b.dds.last_hop.items():
        for j, (_, _, nid) in enumerate(hops[:K_MAX]):
            emb[o, j], msk[o, j], dt[o, j] = h[nid], 1.0, snap[o] - snap[nid]
    assert (dt[msk > 0] > 0).all()
    online = lnn_stage2_online(params, dataclasses.replace(
        cfg, use_pallas=use_pallas), emb, msk, b.graph.features[:n_orders],
        slot_dt=dt)
    np.testing.assert_allclose(np.asarray(online), full[:n_orders], atol=2e-5)


def _reference_probs(ref, params, snap, ents, feats, idx, precision="highest"):
    k_ent, k_t, mask = ref.expected_keys(snap, ents, K_MAX)
    g = ref.Stage1Graph(snap, ents, feats, max_history=MAX_HISTORY,
                        max_deg=MAX_DEG)
    h = ref.stage1(params, "hgt", g, precision)
    nid = g.row_of(k_ent[idx].ravel(), k_t[idx].ravel())
    ok = (nid >= 0) & (mask[idx].ravel() > 0)
    emb = np.zeros((nid.size, H), np.float32)
    emb[ok] = h[nid[ok]]
    logits = ref.stage2_logits(params, "hgt", emb.reshape(len(idx), K_MAX, H),
                               mask[idx], feats[idx], precision)
    return ref.sigmoid(logits), mask[idx]


def _serve(events, params):
    from repro.service import FraudService, ModelSection, ServiceConfig

    sc = ServiceConfig(
        mode="streaming",
        model=ModelSection(gnn_type="hgt", num_gnn_layers=L, hidden_dim=H,
                           num_heads=HEADS, mlp_dims=(16, 8), feat_dim=F,
                           use_pallas=True),
    ).replace(engine={"k_max": K_MAX, "max_history": MAX_HISTORY,
                      "max_deg": MAX_DEG})
    svc = FraudService(sc, params=params).build()
    out = []
    for ev in events:
        out += svc.submit(ev)
    out += svc.drain()
    svc.close()
    return {r.request.tag.order_id: r.score for r in out}


def test_fraud_service_scores_a_stream_as_the_reference_does(ref, events):
    """``gnn_type="hgt"`` through FraudService: ingest, micro-batcher, KV
    gather, the per-slot gaps, the fused stage-2 kernel, the refresher's
    stage-1 kernels; every score against the reference's probability."""
    params = lnn_init(jax.random.PRNGKey(SEED), CFG)
    scores = _serve(events, params)
    snap, ents, feats = _orders(events)
    idx = np.arange(len(events))
    want, mask = _reference_probs(ref, jax.device_get(params), snap, ents,
                                  feats, idx)
    got = np.asarray([scores[e.order_id] for e in events], np.float32)
    assert (mask.sum(1) > 0).sum() > len(events) // 2
    assert float(np.abs(got - want).max()) < LIMITS["score_gap"]


def _perturbed(params):
    """The weights with mu and the skip gates away from their initial 1, so
    that leaving either out shows."""
    rng = np.random.default_rng(0)

    def layer(lp):
        lp = dict(lp)
        lp["mu"] = jnp.asarray(rng.uniform(0.3, 2.0, lp["mu"].shape),
                               jnp.float32)
        return lp

    p = dict(params)
    p["gnn"] = [layer(lp) for lp in params["gnn"]]
    p["last"] = layer(params["last"])
    return p


def _fault(name, params):
    if name == "no_rte":
        def f(lp):
            return dict(lp, rte={"w": jnp.zeros_like(lp["rte"]["w"]),
                                 "b": jnp.zeros_like(lp["rte"]["b"])})
    elif name == "no_mu":
        def f(lp):
            return dict(lp, mu=jnp.ones_like(lp["mu"]))
    else:  # one head: the same weights read as a single 32-wide head
        def f(lp):
            def one(w):
                return jnp.stack([_block_diag(w[r])[None]
                                  for r in range(w.shape[0])])
            return dict(lp, att=one(lp["att"]), msg=one(lp["msg"]),
                        mu=lp["mu"].mean(1, keepdims=True))
    return dict(params, gnn=[f(lp) for lp in params["gnn"]],
                last=f(params["last"]))


@pytest.mark.parametrize("fault", ["no_rte", "no_mu", "one_head", "bf16"])
def test_a_planted_fault_fails_the_limits(ref, events, fault):
    """Each mechanism is load-bearing: a program that drops the relative
    temporal encoding, mu, or the heads, or computes in bfloat16, is
    outside the configuration's limits on stage 1's rows."""
    params = _perturbed(lnn_init(jax.random.PRNGKey(SEED), CFG))
    orders = _orders(events)
    host = jax.device_get(params)
    if fault == "bf16":
        # the reference at one bf16 pass in the program's place
        gap = _stage1_gap(ref, params, *orders, ref_params=host)
        low = _stage1_gap(ref, params, *orders, ref_params=host,
                          precision="bf16")
        assert gap < LIMITS["write_gap"] < low
        return
    bad = _fault(fault, params)
    assert _stage1_gap(ref, bad, *orders, ref_params=host) > LIMITS["write_gap"]


def test_a_traced_hgt_run_names_the_slot_gaps(events, tmp_path):
    """With spans on, every stage-2 launch of an HGT model builds its slot
    gaps under ``s2.slot_dt``, inside the flush and before ``s2.launch``."""
    import glob

    from jax.profiler import ProfileData

    from repro.utils import spans

    params = lnn_init(jax.random.PRNGKey(SEED), CFG)
    spans.enable(True)
    try:
        with jax.profiler.trace(str(tmp_path)):
            _serve(events[:60], params)
    finally:
        spans.enable(False)
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    by: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("s2.slot_dt", "s2.launch"):
                        by.setdefault(e.name, []).append(e.start_ns)
    assert len(by["s2.slot_dt"]) == len(by["s2.launch"]) > 0
    assert all(a < b for a, b in zip(sorted(by["s2.slot_dt"]),
                                     sorted(by["s2.launch"])))


def test_batch_speed_layer_reads_the_gaps_from_the_request_snapshot():
    """Batch mode's exact-key lookup: each slot's gap is the request's
    snapshot minus the key's, as the streaming scorer computes it."""
    from repro.serve.kvstore import KVStore, pack_key
    from repro.serve.lambda_pipeline import SpeedLayer
    from repro.service.types import ScoreRequest

    params = lnn_init(jax.random.PRNGKey(SEED), CFG)
    store = KVStore(H)
    rng = np.random.default_rng(1)
    rows = {(e, t): rng.normal(size=H).astype(np.float32)
            for e, t in ((5, 1), (6, 3), (7, 2))}
    store.put_batch([pack_key(e, t) for e, t in rows], list(rows.values()))
    reqs = [ScoreRequest(features=rng.normal(size=F).astype(np.float32),
                         entity_keys=[(5, 1), (6, 3)], snapshot=4),
            ScoreRequest(features=rng.normal(size=F).astype(np.float32),
                         entity_keys=[(7, 2)], snapshot=9)]
    got = SpeedLayer(params, CFG, store, K_MAX).score(reqs)
    emb = np.zeros((2, K_MAX, H), np.float32)
    msk = np.zeros((2, K_MAX), np.float32)
    dt = np.zeros((2, K_MAX), np.int32)
    for i, r in enumerate(reqs):
        for j, key in enumerate(r.entity_keys):
            emb[i, j], msk[i, j], dt[i, j] = rows[key], 1.0, r.snapshot - key[1]
    want = jax.nn.sigmoid(lnn_stage2_online(
        params, CFG, emb, msk, np.stack([r.features for r in reqs]),
        slot_dt=dt))
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-6)
    with pytest.raises(ValueError, match="snapshot"):
        SpeedLayer(params, CFG, store, K_MAX).score(
            [ScoreRequest(features=reqs[0].features, entity_keys=[(5, 1)])])


def test_a_shard_process_scores_with_the_orders_snapshots(tmp_path):
    """The process backend carries the orders' snapshots in its score frame
    (``order_t``); the shard's scorer then gives what an inline scorer
    over the same store gives for the same flush."""
    from repro.serve.kvstore import KVStore, pack_key
    from repro.stream.microbatch import FlushKeys
    from repro.stream.procpool import ShardServer
    from repro.stream.workers import Stage2Scorer
    from repro.train.checkpoint import save_checkpoint

    params = lnn_init(jax.random.PRNGKey(SEED), CFG)
    path = str(tmp_path / "v0.npz")
    save_checkpoint(path, params)
    srv = ShardServer(wid=0, cfg=CFG, store_cfg=dict(dim=H, num_shards=1,
                                                      shard_by_entity=False),
                      k_max=K_MAX, max_batch=4, model_path=path,
                      model_version=0)
    rng = np.random.default_rng(2)
    keys = np.asarray([pack_key(1, 0), pack_key(2, 1)], np.int64)
    vals = rng.normal(size=(2, H)).astype(np.float32)
    srv.handle({"cmd": "put", "id": 1, "pver": 0, "model_version": 0,
                "stamp": 0.0}, {"keys": keys, "values": vals})
    feats = rng.normal(size=(2, F)).astype(np.float32)
    lists = [[[1, 0], [2, 1]], [[2, 1]]]
    _, secs = srv.handle({"cmd": "score", "id": 2, "version": 0,
                          "keys": lists, "remote": [], "order_t": [3, 7]},
                         {"feats": feats})
    store = KVStore(H)
    store.put_batch(list(keys), list(vals))
    flush = FlushKeys([[tuple(p) for p in ks] for ks in lists])
    flush.order_snapshots = np.asarray([3, 7], np.int32)
    want = Stage2Scorer(params, CFG, store, K_MAX)(feats, flush).wait()[0]
    np.testing.assert_array_equal(dict(secs)["probs"], want)
