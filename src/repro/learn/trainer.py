"""Rolling-window fine-tunes over tap examples (Morpheus-DFP-style).

The trainer holds a bounded buffer of :class:`~repro.learn.tap.TrainingExample`
rows and advances a **rolling window**: once at least ``min_window``
examples are buffered (and ``stride`` new ones since the last fire), it
trains on the newest ``max_window`` examples — deduplicated by order id,
keep-latest, so re-scored orders and label-log corrections supersede
their earlier copies — and records the fire so the next one waits for
another stride of fresh data.

A fine-tune warm-starts from the incumbent's parameters and runs a few
steps of locally-implemented SGD/Adam (no optax) on
:func:`~repro.core.lnn.lnn_loss` over the *window-local* DDS graph: the
window's examples are replayed through a fresh
:class:`~repro.core.dds.IncrementalDDSBuilder`, materialized, and padded
to a power-of-two node budget (bounded jit recompiles, same trick as the
batch-layer refresher).  With ``head="hybrid"`` the tuned stage-1/2
parameters are then frozen and the PR-8 GBDT head is refit on the
window's pre-MLP embeddings (:func:`~repro.models.hybrid.train_hybrid`),
yielding a :class:`~repro.models.hybrid.HybridModel` candidate.
"""
from __future__ import annotations

import os
import shutil
import tempfile
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dds import IncrementalDDSBuilder
from repro.core.graph import pad_graph
from repro.core.hetero import type_code_of
from repro.core.lnn import LNNConfig, lnn_loss, lnn_stage1, lnn_stage2_embed

__all__ = ["FineTuneResult", "RollingWindowTrainer", "WindowPolicy",
           "adam", "sgd"]


# ---------------------------------------------------------------- optimizers
def sgd(lr: float = 1e-2, momentum: float = 0.0):
    """Plain (heavy-ball) SGD as an ``(init_fn, update_fn)`` pair —
    ``update_fn(grads, state, params) -> (new_params, new_state)``.
    Local implementation, no optax (mirrors ``repro.train.optim``)."""

    def init_fn(params):
        return jax.tree_util.tree_map(jnp.zeros_like, params)

    def update_fn(grads, state, params):
        vel = jax.tree_util.tree_map(
            lambda v, g: momentum * v + g, state, grads)
        new = jax.tree_util.tree_map(lambda p, v: p - lr * v, params, vel)
        return new, vel

    return init_fn, update_fn


def adam(lr: float = 1e-3, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-8):
    """Adam as an ``(init_fn, update_fn)`` pair (bias-corrected moments;
    local implementation, no optax)."""

    def init_fn(params):
        z = jax.tree_util.tree_map(jnp.zeros_like, params)
        return {"step": jnp.zeros((), jnp.int32), "mu": z, "nu": z}

    def update_fn(grads, state, params):
        step = state["step"] + 1
        mu = jax.tree_util.tree_map(
            lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
        nu = jax.tree_util.tree_map(
            lambda n, g: b2 * n + (1 - b2) * g * g, state["nu"], grads)
        c1 = 1 - b1 ** step.astype(jnp.float32)
        c2 = 1 - b2 ** step.astype(jnp.float32)
        new = jax.tree_util.tree_map(
            lambda p, m, n: p - lr * (m / c1) / (jnp.sqrt(n / c2) + eps),
            params, mu, nu)
        return new, {"step": step, "mu": mu, "nu": nu}

    return init_fn, update_fn


_OPTIMIZERS = {"sgd": sgd, "adam": adam}


# -------------------------------------------------------------------- policy
@dataclass(frozen=True)
class WindowPolicy:
    """Rolling-window advance policy: fire on ``min_window`` buffered +
    ``stride`` fresh, train on the newest ``max_window`` (``dedup`` =
    keep-latest per order id)."""

    min_window: int = 32
    max_window: int = 256
    stride: int = 32
    dedup: bool = True

    def __post_init__(self):
        if self.min_window < 1:
            raise ValueError("min_window must be >= 1")
        if self.max_window < self.min_window:
            raise ValueError("max_window must be >= min_window")
        if not (1 <= self.stride <= self.max_window):
            raise ValueError("stride must be in [1, max_window]")


@dataclass
class FineTuneResult:
    """One fine-tune outcome: the candidate model plus its training trace."""

    params: dict                 # tuned LNN pytree
    model: object                # what to register: params, or a HybridModel
    head: str                    # 'mlp' | 'hybrid'
    window: int                  # examples actually trained on (post-dedup)
    steps: int
    losses: list                 # per-step lnn_loss values (python floats)


def _pow2_at_least(n: int, floor: int = 64) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


# ------------------------------------------------- pure fine-tune primitives
# Module-level so the inline path and the dedicated trainer process run the
# EXACT same code — in-process fine-tunes are bit-identical to inline ones
# (same ops, same host, same XLA), which the parity tests assert.
def _materialize_window(cfg: LNNConfig, rows: list, *, entity_history: str,
                        max_history, max_deg: int):
    """Window rows ``(snapshot, arrival, entities, features, label)`` →
    window-local DDS graph, padded to pow2 nodes (receptive cones are
    window-local by design: the rolling window IS the context the
    fine-tune sees, matching its serving horizon)."""
    b = IncrementalDDSBuilder(
        feat_dim=cfg.feat_dim, entity_history=entity_history,
        max_history=max_history)
    for snap, _arr, entities, features, label in sorted(
            rows, key=lambda r: (r[0], r[1])):
        b.add_order(entities, snap, features, label)
    dds = b.build()
    pg = pad_graph(dds.coo,
                   num_nodes=_pow2_at_least(dds.coo.num_nodes),
                   max_deg=max_deg)
    return dds, pg


def _fine_tune(params, cfg: LNNConfig, pg, optimizer: str, lr: float,
               steps: int):
    """A few steps of the local optimizer on ``lnn_loss`` over the window
    graph; returns ``(tuned_params, losses)``."""
    init_fn, update_fn = _OPTIMIZERS[optimizer](lr)
    loss_grad = jax.jit(jax.value_and_grad(
        lambda p, g: lnn_loss(p, cfg, g)))
    opt = init_fn(params)
    losses = []
    for _ in range(steps):
        loss, grads = loss_grad(params, pg)
        params, opt = update_fn(grads, opt, params)
        losses.append(float(loss))
    return params, losses


def _train_child_main(conn, spec: dict) -> None:
    """Entry point of the dedicated fine-tune process (spawn start method).

    The window ships as an ``.npz`` blob (flat entity array + offsets for
    the ragged cone lists) and the warm start as a params checkpoint; the
    tuned candidate travels back the same way — an npz file the parent
    loads and feeds into the ordinary registration/promotion path.  Only
    the loss trace crosses the pipe."""
    try:
        from repro.core.lnn import lnn_init
        from repro.train.checkpoint import load_checkpoint, save_checkpoint

        cfg = spec["cfg"]
        blob = np.load(spec["window_path"])
        feats = blob["features"]
        labels = blob["labels"]
        snaps = blob["snapshots"]
        arrivals = blob["arrivals"]
        ent_flat, ent_off = blob["ent_flat"], blob["ent_off"]
        rows = [
            (int(snaps[i]), float(arrivals[i]),
             tuple(int(e) for e in ent_flat[ent_off[i]:ent_off[i + 1]]),
             feats[i], float(labels[i]))
            for i in range(len(labels))
        ]
        template = lnn_init(jax.random.PRNGKey(0), cfg)
        warm = load_checkpoint(spec["warm_path"], template)[0]
        _dds, pg = _materialize_window(
            cfg, rows, entity_history=spec["entity_history"],
            max_history=spec["max_history"], max_deg=spec["max_deg"])
        tuned, losses = _fine_tune(
            warm, cfg, pg, spec["optimizer"], spec["lr"], spec["steps"])
        save_checkpoint(spec["out_path"], tuned)
        conn.send(("ok", losses))
    except Exception as e:   # noqa: BLE001 — the parent re-raises
        try:
            conn.send(("error", f"{type(e).__name__}: {e}"))
        except OSError:
            pass
    finally:
        conn.close()


# ------------------------------------------------------------------- trainer
class RollingWindowTrainer:
    """Accumulate tap examples; fine-tune on rolling windows.

    ``k_max``/``max_deg`` come from the serving engine so the window graph
    is padded the same way the batch layer pads — the candidate sees
    exactly the serving geometry.
    """

    def __init__(self, cfg: LNNConfig, policy: WindowPolicy | None = None, *,
                 optimizer: str = "adam", lr: float = 5e-3, steps: int = 40,
                 head: str = "mlp", gbdt_trees: int = 25, k_max: int = 8,
                 max_deg: int = 32, entity_history: str = "all",
                 max_history: int | None = None, in_process: bool = False):
        if optimizer not in _OPTIMIZERS:
            raise ValueError(f"optimizer must be one of {sorted(_OPTIMIZERS)}")
        if head not in ("mlp", "hybrid"):
            raise ValueError("head must be 'mlp' or 'hybrid'")
        if steps < 1:
            raise ValueError("steps must be >= 1")
        self.cfg = cfg
        self.policy = policy if policy is not None else WindowPolicy()
        self.optimizer, self.lr, self.steps = optimizer, float(lr), int(steps)
        self.head, self.gbdt_trees = head, int(gbdt_trees)
        self.k_max, self.max_deg = int(k_max), int(max_deg)
        self.entity_history, self.max_history = entity_history, max_history
        # in_process=True runs each fine-tune in a dedicated spawn()ed
        # process (off the serving GIL); the GBDT head refit stays in the
        # parent — the booster isn't an npz-serializable pytree
        self.in_process = bool(in_process)
        if self.in_process:
            from repro.stream.procpool import require_cpu_host

            require_cpu_host("learn.train_in_process=True")
        self._buffer: list = []
        self._since_fire: int | None = None   # None = never fired
        self.stats = {"examples": 0, "fires": 0, "last_window": 0,
                      "last_loss": None}

    # -------------------------------------------------------------- buffering
    def add(self, example) -> None:
        """Buffer one tap example (arrival order)."""
        self._buffer.append(example)
        if self._since_fire is not None:
            self._since_fire += 1
        self.stats["examples"] += 1
        # bound memory: the policy can never look past max_window examples,
        # except that dedup needs slack for superseded duplicates
        cap = 4 * self.policy.max_window
        if len(self._buffer) > cap:
            del self._buffer[: len(self._buffer) - cap]

    def extend(self, examples) -> None:
        """Buffer many tap examples."""
        for ex in examples:
            self.add(ex)

    def ready(self) -> bool:
        """True when the rolling window should advance: enough buffered,
        and a full stride of fresh examples since the last fire."""
        if len(self._buffer) < self.policy.min_window:
            return False
        return self._since_fire is None \
            or self._since_fire >= self.policy.stride

    def _window(self) -> list:
        """The newest ``max_window`` examples, deduped keep-latest."""
        ex = self._buffer
        if self.policy.dedup:
            latest: dict[tuple, object] = {}
            for e in ex:     # later entries overwrite earlier (keep-latest)
                latest[(e.order_id, e.seq if e.order_id < 0 else -1)] = e
            ex = list(latest.values())
        return ex[-self.policy.max_window:]

    # ----------------------------------------------------------------- train
    def train(self, params) -> FineTuneResult:
        """Fine-tune ``params`` on the current window; marks the fire."""
        window = self._window()
        if not window:
            raise ValueError("train() with an empty window")
        self._since_fire = 0
        self.stats["fires"] += 1
        self.stats["last_window"] = len(window)

        if self.in_process:
            params, losses = self._train_in_process(params, window)
            dds = pg = None
        else:
            dds, pg = self._materialize(window)
            params, losses = _fine_tune(
                params, self.cfg, pg, self.optimizer, self.lr, self.steps)
        self.stats["last_loss"] = losses[-1]

        model = params
        if self.head == "hybrid":
            if pg is None:
                # GBDT refit runs in the parent either way; rebuild the
                # (deterministic) window graph the child built for itself
                dds, pg = self._materialize(window)
            model = self._fit_hybrid(params, window, dds, pg)
        return FineTuneResult(params=params, model=model, head=self.head,
                              window=len(window), steps=self.steps,
                              losses=losses)

    def _materialize(self, window):
        """Window examples → window-local DDS graph (see
        :func:`_materialize_window`)."""
        rows = [(e.snapshot, e.arrival, e.entities, e.features, e.label)
                for e in window]
        return _materialize_window(
            self.cfg, rows, entity_history=self.entity_history,
            max_history=self.max_history, max_deg=self.max_deg)

    def _train_in_process(self, params, window):
        """Run one fine-tune in a dedicated spawn()ed process.

        Window examples ship as an npz blob (features/labels/snapshots/
        arrivals + flat entities with offsets), the warm start as a params
        checkpoint; the tuned candidate comes back as an npz the parent
        loads into the warm start's pytree structure.  A child that dies
        or reports an error raises — the trainer never silently falls back
        to a stale candidate."""
        from multiprocessing import get_context

        from repro.train.checkpoint import load_checkpoint, save_checkpoint

        tmp = tempfile.mkdtemp(prefix="repro-finetune-")
        try:
            warm_path = os.path.join(tmp, "warm.npz")
            out_path = os.path.join(tmp, "tuned.npz")
            window_path = os.path.join(tmp, "window.npz")
            save_checkpoint(warm_path, params)
            ent_flat: list[int] = []
            ent_off = [0]
            for e in window:
                ent_flat.extend(int(x) for x in e.entities)
                ent_off.append(len(ent_flat))
            np.savez(
                window_path,
                features=np.stack([np.asarray(e.features, np.float32)
                                   for e in window]),
                labels=np.asarray([e.label for e in window], np.float32),
                snapshots=np.asarray([e.snapshot for e in window], np.int64),
                arrivals=np.asarray([e.arrival for e in window], np.float64),
                ent_flat=np.asarray(ent_flat, np.int64),
                ent_off=np.asarray(ent_off, np.int64))
            spec = {
                "cfg": self.cfg, "window_path": window_path,
                "warm_path": warm_path, "out_path": out_path,
                "optimizer": self.optimizer, "lr": self.lr,
                "steps": self.steps, "max_deg": self.max_deg,
                "entity_history": self.entity_history,
                "max_history": self.max_history,
            }
            ctx = get_context("spawn")
            parent_conn, child_conn = ctx.Pipe()
            proc = ctx.Process(target=_train_child_main,
                               args=(child_conn, spec),
                               name="repro-finetune", daemon=True)
            proc.start()
            child_conn.close()
            try:
                status, payload = parent_conn.recv()
            except EOFError:
                raise RuntimeError(
                    "fine-tune process died before returning a result")
            finally:
                proc.join()
                parent_conn.close()
            if status != "ok":
                raise RuntimeError(f"fine-tune process failed: {payload}")
            tuned = load_checkpoint(out_path, params)[0]
            return tuned, payload
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    def _fit_hybrid(self, params, window, dds, pg):
        """Refit the GBDT head on the tuned-then-frozen embedding: stage-1
        over the window graph, each order's final-hop cone gathered into
        the online [B, K, H] layout, then ``train_hybrid`` on the pre-MLP
        stage-2 embeddings."""
        from repro.baselines.gbdt import GBDTConfig
        from repro.models.hybrid import train_hybrid

        h = np.asarray(lnn_stage1(params, self.cfg, pg), np.float32)
        n_ord = dds.num_orders
        hid = h.shape[-1]
        ent = np.zeros((n_ord, self.k_max, hid), np.float32)
        mask = np.zeros((n_ord, self.k_max), np.float32)
        slot = np.full((n_ord, self.k_max), -1, np.int32)
        typed = bool(self.cfg.entity_types)
        for o in range(n_ord):
            for k, (e, _t, nid) in enumerate(dds.last_hop.get(o, [])[: self.k_max]):
                ent[o, k] = h[nid]
                mask[o, k] = 1.0
                if typed:
                    slot[o, k] = type_code_of(e)
        feats = np.asarray(pg.features[:n_ord], np.float32)
        emb = np.asarray(lnn_stage2_embed(
            params, self.cfg, ent, mask, feats,
            slot_type=slot if typed else None), np.float32)
        labels = np.asarray(pg.label[:n_ord], np.float32)
        return train_hybrid(params, self.cfg, emb, labels,
                            gbdt_cfg=GBDTConfig(num_trees=self.gbdt_trees))
