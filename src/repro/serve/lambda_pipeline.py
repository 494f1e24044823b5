"""The Lambda architecture (paper §3.3): batch layer + speed layer.

* :class:`BatchLayer` — periodically refreshes entity embeddings: runs LNN
  stage 1 over every community DDS graph (a pjit-able batch job) and writes
  the ``entity_{t-e}`` embeddings into the KV store.
* :class:`SpeedLayer` — online transaction-risk inference: per checkout
  request, fetch the linked entities' embeddings by key (ONE key-value
  lookup per entity — no graph traversal) and run the one-layer-GNN + MLP
  stage-2 scorer.
* :class:`LambdaPipeline` — wires both; ``score_equivalence_check`` proves
  the two-stage path reproduces the monolithic full-graph forward exactly
  (the paper's correctness argument for deploying the split).
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lnn import (
    LNNConfig,
    lnn_forward,
    lnn_stage1,
    lnn_stage2_online,
)
from repro.serve.kvstore import KVStore, pack_key
from repro.service.types import ScoreRequest


@dataclass
class BatchLayer:
    """Periodic batch-layer refresh: ``refresh(batches)`` runs jitted LNN
    stage 1 over each community's padded graph and writes every
    ``(entity, t)`` snapshot embedding into ``store`` under its packed key.

    ``batches`` are community batches (``b.graph`` PaddedGraph + ``b.dds``
    build record) as produced by ``repro.data.build_communities``.
    """

    params: object
    cfg: LNNConfig
    store: KVStore
    model_version: int = 0

    def __post_init__(self):
        self._stage1 = jax.jit(lambda p, g: lnn_stage1(p, self.cfg, g))

    def set_model(self, params, model_version: int) -> None:
        """Swap to a new parameter version: subsequent refreshes compute and
        stamp embeddings under it (stage 1 is jitted over params-as-args, so
        no recompile)."""
        self.params = params
        self.model_version = int(model_version)

    def refresh(self, batches) -> dict:
        """Run stage 1 over all communities, push entity embeddings to the KV
        store.  Returns refresh stats (the paper's 'periodical inference')."""
        t0 = time.time()
        n_written = 0
        for b in batches:
            h = np.asarray(self._stage1(self.params, b.graph))
            # write every entity-snapshot vertex (key = (global entity, t))
            # as ONE batched put: a single store lock/clock acquisition per
            # community instead of one per embedding
            items = list(b.dds.entity_snap_ids.items())
            keys = [pack_key(self._global_entity(b, ent), t)
                    for (ent, t), _ in items]
            n_written += self.store.put_batch(
                keys, (h[nid] for _, nid in items),
                model_version=self.model_version)
        return {"entities_written": n_written, "seconds": time.time() - t0,
                "store_size": len(self.store)}

    @staticmethod
    def _global_entity(b, local_ent: int) -> int:
        # communities keep a local->global entity map when built from a
        # partition; fall back to local ids for single-community graphs
        m = getattr(b, "global_entity_ids", None)
        return int(m[local_ent]) if m is not None else int(local_ent)


@dataclass
class SpeedLayer:
    """Online transaction-risk scorer: ``score(requests)`` maps a list of
    ``{'features': [F], 'entity_keys': [(entity, t_e), ...]}`` dicts to
    fraud probabilities via at most ``k_max`` KV lookups per request plus a
    single stage-2 dispatch.

    The whole online compute (order tower + masked aggregation + last GNN
    layer + MLP head) is one jitted call of ``lnn_stage2_online``; with
    ``cfg.use_pallas`` that call is the fused ``kernels.stage2_score``
    Pallas launch.
    """

    params: object
    cfg: LNNConfig
    store: KVStore
    k_max: int = 8
    model_version: int = 0

    def __post_init__(self):
        self._stage2 = jax.jit(
            lambda p, emb, mask, feats, dt=None: lnn_stage2_online(
                p, self.cfg, emb, mask, feats, slot_dt=dt
            )
        )

    def set_model(self, params, model_version: int) -> None:
        """Swap to a new parameter version (params are jit arguments, so the
        compiled stage-2 cache is reused across versions)."""
        self.params = params
        self.model_version = int(model_version)

    def score(self, requests: list) -> np.ndarray:
        """requests: typed :class:`~repro.service.types.ScoreRequest`s (the
        legacy ``{'features': [F], 'entity_keys': [(ent, t_e), ...]}`` dicts
        are still accepted).

        Returns fraud probabilities.  This is the checkout-approval hot path:
        K key-value lookups + one fused jit call; no graph database."""
        reqs = [ScoreRequest.from_legacy(r) for r in requests]
        feats = jnp.asarray(np.stack([r.features for r in reqs]))
        key_lists = [
            [pack_key(e, t) for (e, t) in r.entity_keys] for r in reqs
        ]
        emb, mask = self.store.lookup_batch(key_lists, self.k_max)
        args = (self.params, jnp.asarray(emb), jnp.asarray(mask), feats)
        if self.cfg.gnn_type == "hgt":
            from repro.stream.workers import slot_gaps

            if any(r.entity_keys and r.snapshot < 0 for r in reqs):
                raise ValueError("an hgt model needs ScoreRequest.snapshot")
            # exact keys: a served slot is 0 snapshots stale
            stale = np.where(mask > 0, 0, -1).astype(np.int32)
            args += (slot_gaps([r.snapshot for r in reqs],
                               [r.entity_keys for r in reqs], stale,
                               self.k_max),)
        logits = self._stage2(*args)
        return np.asarray(jax.nn.sigmoid(logits))


@dataclass
class LambdaPipeline:
    """Both Lambda halves wired over one shared ``KVStore``: ``refresh``
    delegates to the :class:`BatchLayer`, ``score`` to the
    :class:`SpeedLayer`, and ``score_equivalence_check`` replays every
    order with history through the real store to bound the two-stage vs
    monolithic score gap.

    .. deprecated::
        ``LambdaPipeline`` is a compatibility shim.  Construct a
        :class:`repro.service.FraudService` with ``mode="batch"`` instead —
        it wraps the same :class:`BatchLayer`/:class:`SpeedLayer` over the
        same store (bit-identical scores, proven in
        ``tests/test_service.py``) and adds the lifecycle, hot-swap, and
        admission-control surface.
    """

    params: object
    cfg: LNNConfig
    k_max: int = 8
    store: KVStore | None = None

    def __post_init__(self):
        warnings.warn(
            "LambdaPipeline is deprecated; use "
            "repro.service.FraudService(mode='batch') — see docs/serving_api.md",
            DeprecationWarning, stacklevel=2,
        )
        if self.store is None:
            self.store = KVStore(self.cfg.hidden_dim)
        self.batch_layer = BatchLayer(self.params, self.cfg, self.store)
        self.speed_layer = SpeedLayer(self.params, self.cfg, self.store, self.k_max)

    def refresh(self, batches):
        return self.batch_layer.refresh(batches)

    def score(self, requests):
        return self.speed_layer.score(requests)

    # ------------------------------------------------------------------ checks
    def score_equivalence_check(self, batches, atol=1e-4) -> float:
        """Max |two-stage online score - monolithic forward score| over all
        orders with history.  Proves the lambda split exact end-to-end
        (through the real KV store, not in-memory shortcuts)."""
        return split_equivalence_check(self.score, self.params, self.cfg,
                                       batches, atol)


def _batch_history_requests(b) -> tuple[list[ScoreRequest], list[int]]:
    """(typed requests, their order rows) for one community batch — the one
    place the speed-layer request construction from ``b.dds.last_hop``
    lives, so the demos/benches and the equivalence check can never drift
    onto different request shapes."""
    requests, rows = [], []
    for o, hops in b.dds.last_hop.items():
        keys = [(BatchLayer._global_entity(b, ent), t) for ent, t, _ in hops]
        requests.append(ScoreRequest(
            features=np.asarray(b.graph.features[o]), entity_keys=keys))
        rows.append(o)
    return requests, rows


def history_requests(batches) -> list[ScoreRequest]:
    """Typed speed-layer requests for every order with history across the
    community batches — what the demos and benchmarks used to hand-build
    from ``b.dds.last_hop`` with dicts."""
    return [r for b in batches for r in _batch_history_requests(b)[0]]


def split_equivalence_check(score_fn, params, cfg: LNNConfig, batches,
                            atol: float = 1e-4) -> float:
    """Max |online score - monolithic forward| over all orders with history,
    for ANY scorer with the speed-layer signature (``score_fn(requests) ->
    probs``) — shared by the legacy pipeline and the ``FraudService``
    facade so both prove the same bound through the same replay."""
    fwd = jax.jit(lambda p, g: lnn_forward(p, cfg, g))
    worst = 0.0
    for b in batches:
        requests, rows = _batch_history_requests(b)
        if not requests:
            continue
        full = np.asarray(jax.nn.sigmoid(fwd(params, b.graph)))
        online = np.asarray(score_fn(requests))
        worst = max(worst, float(np.abs(online - full[rows]).max()))
    if worst > atol:
        raise AssertionError(f"lambda split mismatch: {worst} > {atol}")
    return worst
