"""How ``correct`` is decided: the timed path's answers against the plain
reference (``configs/<reference>.py``), number by number, each against its
limit from the configuration file.

What is compared, for every order answered in the window:

* ``unanswered``: orders submitted in the window that got no score (0);
* ``key_mismatch``: KV slots whose key or mask differs from the keys the
  reference derives from the orders (0);
* ``kv_gap``: the largest gap between an embedding the order read from the
  KV store and the reference's stage-1 row for that key, over the largest
  reference row;
* ``score_gap``: the largest gap between the fraud probability served and
  the reference's, computed from the reference's own rows;

and, where the window closes snapshots, for every ``(entity, snapshot)``
pair of a closed snapshot:

* ``write_missing``: pairs no refresh of the window wrote (0);
* ``write_gap``: the largest gap between a written row and the
  reference's, over the largest reference row.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclass
class Observed:
    """What the timed path (or the control, in its place) produced."""

    index: np.ndarray        # [n] window index of each answered order
    keys_ent: np.ndarray     # [n, K] KV slot keys (entity, t), 0 where empty
    keys_t: np.ndarray
    mask: np.ndarray         # [n, K] 1 where the slot was served
    emb: np.ndarray          # [n, K, H] embeddings read
    prob: np.ndarray         # [n] fraud probability served
    write_ent: np.ndarray    # [m] pairs the window's refreshes wrote
    write_t: np.ndarray
    write_rows: np.ndarray   # [m, H]
    attempted: int


def load_reference(config: dict, root: Path = HERE):
    path = root / "configs" / f"{config['reference']}.py"
    spec = importlib.util.spec_from_file_location(
        f"onchip_ref_{config['reference']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Expected:
    """The reference's answers for one run's orders."""

    def __init__(self, ref, config: dict, seed: int, orders, window_start: int,
                 closed_snapshots, precision: str = "highest"):
        model = config["service"]["model"]
        k_max = config["service"]["engine"]["k_max"]
        eng = config["service"]["engine"]
        self.ref, self.model = ref, model
        self.precision = precision
        self.params = ref.init_params(seed, model)
        ent, t, mask = ref.expected_keys(orders.snapshot, orders.entities, k_max)
        self.keys_ent, self.keys_t = ent[window_start:], t[window_start:]
        self.mask = mask[window_start:]
        self.features = orders.features[window_start:]
        closed = np.asarray(sorted(closed_snapshots), np.int64)
        need_t = max(int(self.keys_t.max(initial=0)),
                     int(closed.max(initial=0)))
        keep = orders.snapshot <= need_t
        self.graph = ref.Stage1Graph(
            orders.snapshot[keep], orders.entities[keep], orders.features[keep],
            max_history=eng["max_history"], max_deg=eng["max_deg"])
        self.h = ref.stage1(self.params, model["gnn_type"], self.graph,
                            precision)
        pairs = self.graph.pairs
        written = np.nonzero(np.isin(pairs[:, 1], closed))[0]
        self.write_pairs = pairs[written]
        self.write_rows = self.h[self.graph.n_orders + written]

    def rows(self, ent, t, mask):
        """Reference stage-1 rows ``[..., H]`` for keys (zero where masked
        or absent)."""
        shape = ent.shape
        nid = self.graph.row_of(ent.ravel(), t.ravel())
        ok = (nid >= 0) & (mask.ravel() > 0)
        out = np.zeros((nid.size, self.h.shape[1]), np.float32)
        out[ok] = self.h[nid[ok]]
        return out.reshape(shape + (self.h.shape[1],))

    def probs(self, index):
        emb = self.rows(self.keys_ent[index], self.keys_t[index],
                        self.mask[index])
        logits = self.ref.stage2_logits(self.params, self.model["gnn_type"],
                                        emb, self.mask[index],
                                        self.features[index], self.precision)
        return self.ref.sigmoid(logits), emb


def control_observed(exp_low: Expected, attempted: int) -> Observed:
    """The reference at the lower precision, put in the program's place."""
    idx = np.arange(attempted)
    prob, emb = exp_low.probs(idx)
    ent, t = exp_low.write_pairs[:, 0], exp_low.write_pairs[:, 1]
    return Observed(idx, exp_low.keys_ent[idx], exp_low.keys_t[idx],
                    exp_low.mask[idx], emb, prob, ent, t, exp_low.write_rows,
                    attempted)


def compare(obs: Observed, exp: Expected, closes: bool) -> dict:
    """The compared numbers, by name."""
    out = {"unanswered": float(obs.attempted - len(np.unique(obs.index)))}
    idx = obs.index
    key_bad = ((obs.mask != exp.mask[idx])
               | ((obs.mask > 0) & ((obs.keys_ent != exp.keys_ent[idx])
                                    | (obs.keys_t != exp.keys_t[idx]))))
    out["key_mismatch"] = float(np.count_nonzero(key_bad))
    prob, emb = exp.probs(idx)
    scale = max(float(np.abs(emb).max(initial=0.0)), 1e-30)
    out["kv_gap"] = float(np.abs(obs.emb - emb).max(initial=0.0)) / scale
    out["score_gap"] = float(np.abs(obs.prob.astype(np.float64)
                                    - prob.astype(np.float64)).max(initial=0.0))
    if closes:
        want = exp.write_pairs
        have = obs.write_ent.astype(np.int64) * (1 << 20) + obs.write_t
        wcode = want[:, 0].astype(np.int64) * (1 << 20) + want[:, 1]
        order = np.argsort(have)
        hs = have[order]
        pos = np.minimum(np.searchsorted(hs, wcode), max(len(hs) - 1, 0))
        found = (hs[pos] == wcode) if len(hs) else np.zeros(len(wcode), bool)
        out["write_missing"] = float(np.count_nonzero(~found))
        got = obs.write_rows[order[pos[found]]]
        ref_rows = exp.write_rows[found]
        wscale = max(float(np.abs(exp.write_rows).max(initial=0.0)), 1e-30)
        out["write_gap"] = float(np.abs(got - ref_rows).max(initial=0.0)) / wscale
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number beside its limit.  A number over its
    limit, or one with no limit, is not correct."""
    checks = {}
    ok = True
    for name, value in numbers.items():
        lim = limits.get(name)
        passed = lim is not None and np.isfinite(value) and value <= lim
        ok &= bool(passed)
        checks[name] = {"value": value, "limit": lim}
    return ok, checks
