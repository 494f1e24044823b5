"""The one traffic generator: a traffic file of parameters -> a checkout stream.

A traffic file (``traffic/<name>.json``) holds only numbers.  This module
turns it, a configuration's feature width and a seed into three plain
arrays of orders, in arrival order:

* ``history``: the orders ingested during set-up, one snapshot (day) after
  another, so that the KV store holds embeddings for every user's entities;
* ``prime``: one order in the first window snapshot, ingested last in
  set-up, so that the last history day closes (and is refreshed) before the
  window opens;
* ``window``: the orders of the timed window, each with its due time in
  seconds from the window's start.

The entity model is the synthetic marketplace's (``repro.data.synth``):
every user owns seven entities (shipping address, email, IP, device, phone,
payment token, account), some users share an IP (offices, households), and
users behind a carrier NAT share a small pool of IPs, which merges all of
them into one giant connected community.

The structure is fixed by the traffic file alone, never by the seed: which
user orders on which day, which IP each order uses, and how many orders
fall into each snapshot.  So every seed gives the same communities, the
same padded refresh bins and the same number of orders per snapshot.  The
seed draws the entity-id offset, the order ids, the features, the order of
the orders within each snapshot, and the order of the inter-arrival gaps
(the multiset of gaps is the same for every seed).
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path

import numpy as np

ENTITY_TYPES = ("ship_addr", "email", "ip", "device", "phone", "pay_token",
                "account")
IP = ENTITY_TYPES.index("ip")
SLOTS_PER_USER = 8          # entity-id stride per user (7 used)
HERE = Path(__file__).resolve().parent


@dataclass
class Orders:
    """Orders in arrival order: ``snapshot [n]``, ``entities [n, 7]``,
    ``features [n, F]`` float32, ``order_id [n]``, ``due [n]`` seconds
    from the window's start (0 for set-up orders)."""

    snapshot: np.ndarray
    entities: np.ndarray
    features: np.ndarray
    order_id: np.ndarray
    due: np.ndarray

    def __len__(self) -> int:
        return int(self.snapshot.shape[0])

    def take(self, n: int) -> "Orders":
        """The first ``n`` orders."""
        return Orders(self.snapshot[:n], self.entities[:n], self.features[:n],
                      self.order_id[:n], self.due[:n])

    @staticmethod
    def concat(parts: list) -> "Orders":
        return Orders(*(np.concatenate([getattr(p, f) for p in parts])
                        for f in ("snapshot", "entities", "features",
                                  "order_id", "due")))


@dataclass
class Stream:
    history: Orders
    prime: Orders
    window: Orders
    params: dict
    snapshot_s: float | None      # window snapshot length; None = one snapshot
    first_window_snapshot: int

    def window_closes(self) -> list:
        """``(boundary_s, closed_snapshot)`` for each snapshot the window
        closes: the first order of every later snapshot is due exactly at
        its boundary and closes the one before."""
        if self.snapshot_s is None:
            return []
        snaps = np.unique(self.window.snapshot)
        return [((int(s) - self.first_window_snapshot) * self.snapshot_s,
                 int(s) - 1) for s in snaps[1:]]


def find_traffic(name: str, root: Path = HERE) -> Path:
    """The traffic file of that name under ``root/traffic``."""
    p = root / "traffic" / f"{name}.json"
    if not p.is_file():
        raise FileNotFoundError(f"no traffic file {p}")
    return p


def load_traffic(name: str, root: Path = HERE) -> dict:
    with open(find_traffic(name, root)) as f:
        return json.load(f)


# ----------------------------------------------------------------- population
def _population(pop: dict):
    """Structural user table: ``(n_users, ip_of_user, nat_users)`` where
    ``ip_of_user[u]`` is the IP entity index (relative) every order of a
    non-NAT user uses, and NAT users draw theirs from the pool per order."""
    n = int(pop["users"])
    ip = np.arange(n, dtype=np.int64) * SLOTS_PER_USER + IP
    next_id = n * SLOTS_PER_USER
    u = 0
    for grp in pop.get("shared_ip_groups", []):
        for _ in range(int(grp["groups"])):
            ip[u:u + int(grp["users"])] = next_id
            next_id += 1
            u += int(grp["users"])
    nat = pop.get("nat_pool") or {"users": 0, "ips": 0}
    nat_users = np.arange(u, u + int(nat["users"]), dtype=np.int64)
    nat_ips = next_id + np.arange(int(nat["ips"]), dtype=np.int64)
    if u + len(nat_users) > n:
        raise ValueError("shared-IP groups and the NAT pool exceed the users")
    return n, ip, nat_users, nat_ips


def _entities(users: np.ndarray, order_index: np.ndarray, ip_of_user,
              nat_users, nat_ips) -> np.ndarray:
    """``[n, 7]`` relative entity ids of the orders of ``users``; a NAT
    user's ``j``-th order uses pool IP ``(user + j) mod ips``."""
    ents = users[:, None] * SLOTS_PER_USER + np.arange(len(ENTITY_TYPES))
    ents[:, IP] = ip_of_user[users]
    if len(nat_users):
        is_nat = (users >= nat_users[0]) & (users <= nat_users[-1])
        pick = (users[is_nat] + order_index[is_nat]) % len(nat_ips)
        ents[is_nat, IP] = nat_ips[pick]
    return ents


def _gaps(n: int, span: float) -> np.ndarray:
    """``n`` exponential quantile gaps scaled to sum to ``span``: the
    inter-arrival times of a Poisson stream, as a fixed multiset."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return q * (span / q.sum())


def generate(params: dict, feat_dim: int, seed: int, seconds: float,
             rate_per_s: float | None = None) -> Stream:
    """Build the stream of one run.  ``rate_per_s`` overrides the traffic
    file's rate (the knee sweep only)."""
    rng = np.random.default_rng(int(seed))
    n_users, ip_of_user, nat_users, nat_ips = _population(params["population"])
    offset = int(rng.integers(1, 2 ** 30)) * SLOTS_PER_USER
    hist_p = params["history"]
    days, per_user = int(hist_p["days"]), int(hist_p["orders_per_user"])
    stride = max(1, days // per_user)

    # --- history: user u orders on days (u + j * stride) mod days
    users = np.repeat(np.arange(n_users, dtype=np.int64), per_user)
    j = np.tile(np.arange(per_user, dtype=np.int64), n_users)
    day = (users + j * stride) % days
    ents = _entities(users, j, ip_of_user, nat_users, nat_ips)
    order = np.lexsort((rng.permutation(len(users)), day))
    hist_snap, hist_ents = day[order], ents[order]
    history_uses = np.full(n_users, per_user, np.int64)

    # --- window: fixed users per snapshot, shuffled within the snapshot
    win = params["window"]
    first = days
    if win["arrivals"] == "backlog":
        snap_s, n_snaps, per_snap = None, 1, int(win["orders"])
    else:
        rate = float(rate_per_s if rate_per_s is not None
                     else win["rate_per_s"])
        snap_s = win.get("snapshot_s")
        span = float(snap_s) if snap_s else float(seconds)
        n_snaps = max(1, int(math.floor(seconds / span + 1e-9)))
        per_snap = max(1, int(round(rate * span)))
    nat_share = float(win.get("nat_share", 0.0))
    regular = np.setdiff1d(np.arange(n_users, dtype=np.int64), nat_users)
    w_users, w_snap, w_due = [], [], []
    k_reg = k_nat = 0
    nat_acc = 0.0
    for s in range(n_snaps):
        us = np.empty(per_snap, np.int64)
        for i in range(per_snap):
            nat_acc += nat_share
            if len(nat_users) and nat_acc >= 1.0:
                nat_acc -= 1.0
                us[i] = nat_users[k_nat % len(nat_users)]
                k_nat += 1
            else:
                us[i] = regular[k_reg % len(regular)]
                k_reg += 1
        w_users.append(rng.permutation(us))
        w_snap.append(np.full(per_snap, first + s, np.int64))
        if win["arrivals"] == "backlog":
            w_due.append(np.zeros(per_snap))
        else:
            g = rng.permutation(_gaps(per_snap, span))
            w_due.append(s * span + np.concatenate([[0.0], np.cumsum(g[:-1])]))
    w_users = np.concatenate(w_users)
    w_order_index = np.empty_like(w_users)
    uses = history_uses.copy()
    for i, u in enumerate(w_users):        # per-user order count so far
        w_order_index[i] = uses[u]
        uses[u] += 1
    w_ents = _entities(w_users, w_order_index, ip_of_user, nat_users, nat_ips)

    # --- prime: the last regular user's next order, in the first window day
    pu = np.asarray([regular[-1]], np.int64)
    p_ents = _entities(pu, np.asarray([per_user]), ip_of_user, nat_users,
                       nat_ips)

    n_hist, n_win = len(hist_snap), len(w_users)
    feats = rng.standard_normal((n_hist + 1 + n_win, feat_dim),
                                dtype=np.float32)
    ids = rng.permutation(n_hist + 1 + n_win).astype(np.int64)

    def part(snap, ents, lo, hi, due):
        return Orders(snap.astype(np.int64), ents + offset, feats[lo:hi],
                      ids[lo:hi], due.astype(np.float64))

    return Stream(
        history=part(hist_snap, hist_ents, 0, n_hist, np.zeros(n_hist)),
        prime=part(np.asarray([first]), p_ents, n_hist, n_hist + 1,
                   np.zeros(1)),
        window=part(np.concatenate(w_snap), w_ents, n_hist + 1,
                    n_hist + 1 + n_win, np.concatenate(w_due)),
        params=params, snapshot_s=float(snap_s) if snap_s else None,
        first_window_snapshot=first)


def community_nodes(orders: Orders) -> dict:
    """DDS node count of every connected community of ``orders`` (two
    nodes per order, one per distinct ``(entity, snapshot)`` pair), keyed
    by the community's smallest entity id.  Used to check that the giant
    community's padded bin does not depend on the seed."""
    ents = orders.entities
    uniq, inv = np.unique(ents, return_inverse=True)
    inv = inv.reshape(ents.shape)
    parent = np.arange(len(uniq))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for row in inv:
        r0 = find(row[0])
        for e in row[1:]:
            r = find(e)
            if r != r0:
                parent[max(r, r0)] = min(r, r0)
                r0 = min(r, r0)
    root = np.asarray([find(i) for i in range(len(uniq))])
    pairs = np.unique(np.stack([inv.ravel(),
                                np.repeat(orders.snapshot, ents.shape[1])], 1),
                      axis=0)
    nodes = np.bincount(root[pairs[:, 0]], minlength=len(uniq))
    nodes += 2 * np.bincount(root[inv[:, 0]], minlength=len(uniq))
    return {int(uniq[r]): int(nodes[r]) for r in np.unique(root)}


def pow2_bin(nodes: int, floor: int = 64) -> int:
    """The padded node budget of a refresh bin holding ``nodes`` nodes."""
    b = floor
    while b < nodes:
        b *= 2
    return b
