"""The crash-matrix: kill the engine at every registered fault-injection
boundary and prove recovery is *bit-identical* to an uninterrupted run.

For each :data:`repro.utils.crashpoint.CRASH_POINTS` name, ``N=1`` and
``N=4`` workers, with a mid-stream model hot-swap and a mid-stream durable
checkpoint in every scenario:

* the armed boundary actually fires (a sweep entry that never crashes
  would silently test nothing);
* restore + WAL-suffix replay + resumed feed produces the SAME
  ``order_id -> (score, model_version)`` map as the uninterrupted oracle —
  no event lost, none double-scored, duplicates delivered bit-identically;
* the KV store holds the SAME bytes entry-for-entry.

The scenarios place the crash at materially different stream positions
(before/after the checkpoint, before/after the hot-swap, inside the
checkpoint write itself) via per-point hit counts — recovery must be exact
regardless of where the process dies.
"""
import jax
import pytest

from repro.core import LNNConfig, lnn_init
from repro.data import SynthConfig, generate_event_stream
from repro.service import FraudService, ModelSection, ServiceConfig
from repro.utils import crashpoint
from repro.utils.crashpoint import CRASH_POINTS

from faultinject import (
    drive,
    merge_responses,
    run_uninterrupted,
    run_with_crash,
    store_contents,
)

N_EVENTS = 60
SWAP_AT = 25          # hot-swap to version 1 after submitting events[25]
CHECKPOINT_AT = 12    # durable checkpoint after submitting events[12]

#: hit count per point, tuned so the crash lands mid-stream (after the
#: checkpoint where the firing rate allows) rather than on the first event
_HITS = {
    "wal.append.before": 40,   # fires per WAL record (~62 total)
    "wal.append.after": 40,
    "ingest.before": 35,       # fires per submitted event (60 total)
    "ingest.after": 35,
    "flush.before_score": 8,   # fires per micro-batch flush (~15 total)
    "flush.in_flight": 8,
    "flush.after_score": 8,
    "refresh.before_stage1": 6,   # fires per non-empty refresh window
    "refresh.before_puts": 6,
    "refresh.after": 6,
    "kv.put_batch.before": 5,  # fires per refresh KV write batch
    "kv.put_batch.after": 5,
    "checkpoint.before": 1,    # fires inside the checkpoint at event 12
    "checkpoint.mid": 1,
    "checkpoint.after": 1,
}

#: "worker_kill" is a *shard-process* death the pool absorbs (SIGKILL +
#: restore + exactly-once re-dispatch), not a parent crash the WAL harness
#: recovers from — it gets its own process-backend test below
_PARENT_POINTS = [p for p in CRASH_POINTS if p != "worker_kill"]


@pytest.fixture(scope="module")
def world():
    events, g, _ = generate_event_stream(
        SynthConfig(num_users=40, num_rings=2, feature_noise=0.8, seed=3),
        rate_per_s=500.0)
    events = events[:N_EVENTS]
    cfg = LNNConfig(num_gnn_layers=2, hidden_dim=8,
                    feat_dim=g.order_features.shape[1], mlp_dims=(8,))
    params = lnn_init(jax.random.PRNGKey(0), cfg)
    swap_params = lnn_init(jax.random.PRNGKey(7), cfg)
    return events, cfg, params, swap_params


def _maker(cfg, params, num_workers):
    sc = ServiceConfig(
        mode="streaming", model=ModelSection.from_lnn_config(cfg),
    ).replace(engine={"num_workers": num_workers, "max_batch": 4})
    return lambda: FraudService(sc, params=params).build()


@pytest.fixture(scope="module")
def baselines(world):
    """Uninterrupted oracle (scores + KV bytes) per worker count."""
    events, cfg, params, swap_params = world
    out = {}
    for n in (1, 4):
        out[n] = run_uninterrupted(
            _maker(cfg, params, n), events,
            swap=(SWAP_AT, swap_params, 1))
    return out


def _sweep(world, baselines, tmp_path, point, num_workers):
    events, cfg, params, swap_params = world
    res = run_with_crash(
        _maker(cfg, params, num_workers), events, str(tmp_path), point,
        hit=_HITS[point], swap=(SWAP_AT, swap_params, 1),
        checkpoint_at=CHECKPOINT_AT)
    assert res["crashed"] is not None, \
        f"{point}: armed boundary never fired — the sweep tested nothing"
    assert res["crashed"].point == point
    assert crashpoint.armed() is None
    base_scores, base_store = baselines[num_workers]
    assert set(res["scores"]) == set(base_scores), \
        f"{point}: event lost or invented across crash-restore-replay"
    diverged = [o for o in base_scores if res["scores"][o] != base_scores[o]]
    assert not diverged, \
        f"{point}: {len(diverged)} scores diverged after recovery"
    assert res["store"] == base_store, \
        f"{point}: KV-store bytes diverged after recovery"


@pytest.mark.parametrize("point", _PARENT_POINTS)
def test_crash_matrix_single_worker(world, baselines, tmp_path, point):
    _sweep(world, baselines, tmp_path, point, num_workers=1)


@pytest.mark.parametrize("point", _PARENT_POINTS)
def test_crash_matrix_four_workers(world, baselines, tmp_path, point):
    _sweep(world, baselines, tmp_path, point, num_workers=4)


@pytest.mark.parametrize("num_workers", [1, 4])
def test_worker_kill_process_backend(world, baselines, num_workers):
    """SIGKILL a shard process mid-stream (the ``worker_kill`` crash point
    turns the k-th SCORE post into a kill of its target child).  The pool
    must restore the shard from its last snapshot + put-journal suffix and
    re-dispatch the in-flight flush exactly once: scores AND KV bytes stay
    bit-identical to the inline oracle, with the restart visible in the
    per-worker stats."""
    events, cfg, params, swap_params = world
    sc = ServiceConfig(
        mode="streaming", model=ModelSection.from_lnn_config(cfg),
    ).replace(engine={"num_workers": num_workers, "max_batch": 4},
              workers={"backend": "process"})
    svc = FraudService(sc, params=params).build()
    try:
        crashpoint.arm("worker_kill", hit=8)
        try:
            responses = drive(svc, events, swap=(SWAP_AT, swap_params, 1))
        finally:
            crashpoint.disarm()
        pool = svc.engine.pool
        restarts = sum(row["restarts"] for row in pool.worker_summary())
        assert restarts >= 1, "armed worker_kill never killed a child"
        assert pool.dead_workers() == 0
        base_scores, base_store = baselines[num_workers]
        scores = merge_responses({}, responses)
        assert scores == base_scores, \
            "scores diverged across worker kill + restore"
        assert store_contents(svc.store) == base_store, \
            "KV-store bytes diverged across worker kill + restore"
    finally:
        svc.close()


def test_no_crash_wal_run_matches_oracle(world, baselines, tmp_path):
    """The WAL + checkpoint machinery itself must not perturb scoring:
    an *uninterrupted* WAL-enabled run (with a mid-stream checkpoint and
    hot-swap) is bit-identical to the bare oracle."""
    events, cfg, params, swap_params = world
    res = run_with_crash(
        _maker(cfg, params, 1), events, str(tmp_path),
        # armed point whose hit count is beyond the run -> never fires
        "checkpoint.before", hit=99,
        swap=(SWAP_AT, swap_params, 1), checkpoint_at=CHECKPOINT_AT)
    assert res["crashed"] is None
    base_scores, base_store = baselines[1]
    assert res["scores"] == base_scores
    assert res["store"] == base_store
