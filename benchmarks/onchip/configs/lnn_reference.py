"""Plain reference of the LNN fraud scorer (arXiv 2110.04559), in numpy.

It imports nothing of the program and takes nothing the program made.
From the orders of a run (``traffic_gen.Orders``) and the seed it builds:

* the weights, with the key schedule of an ``lnn_init(PRNGKey(seed))``
  (the same ``jax.random`` draws, made here in a jitted call of its own);
* the DDS graph's stage-1 part (paper section 3.2): a shadow clone per
  order, one vertex per active ``(entity, snapshot)`` pair, shadow <->
  entity edges within a snapshot and entity-history edges from the
  entity's last ``max_history`` active snapshots plus a self loop, each
  vertex keeping at most ``max_deg`` in-edges, the most recent sources
  first;
* stage 1 (input projection and the first L-1 GNN layers) as edge-list
  sums, O(E * H) and no padding;
* the speed-layer keys of every order (each linked entity's latest
  strictly past active snapshot) and stage 2 (order tower, masked mean or
  attention over the KV slots, last layer, MLP head).

``precision`` sets how every matrix product rounds its operands:
``"highest"`` is float32 throughout; ``"high"`` splits each float32
operand into two bfloat16 parts and keeps three of the four partial
products (XLA's three-pass ``Precision.HIGH``); ``"bf16"`` is a single
bfloat16 pass.  The control of ``correct`` runs this reference at ``high``.
"""
from __future__ import annotations

import numpy as np
from ml_dtypes import bfloat16

# node types and edge types of the DDS graph (paper Table 2)
ORDER, SHADOW, ENTITY = 0, 1, 2
SHADOW_TO_ENTITY, ENTITY_TO_SHADOW, ENTITY_HIST, ENTITY_TO_ORDER = 0, 1, 2, 3
NUM_ETYPES = 4


# ------------------------------------------------------------------ weights
def init_params(seed: int, model: dict) -> dict:
    """The weights an ``lnn_init(jax.random.PRNGKey(seed), cfg)`` draws, as
    float32 numpy arrays.  They are drawn with ``jax.random`` in one jitted
    call on JAX's default device, the device the program draws its own on:
    the chip's normal sampler and the CPU's differ by about 1e-6 relative,
    which would show in every comparison."""
    import jax
    import jax.numpy as jnp

    gnn, L, H = model["gnn_type"], model["num_gnn_layers"], model["hidden_dim"]
    F, mlp_dims = model["feat_dim"], tuple(model["mlp_dims"])

    def glorot(k, shape):
        return jax.random.normal(k, shape, jnp.float32) * jnp.sqrt(
            2.0 / (shape[-2] + shape[-1]))

    def layer(k):
        if gnn == "gcn":
            ks = jax.random.split(k, NUM_ETYPES + 1)
            return {"w_self": glorot(ks[0], (H, H)),
                    "w_nbr": jnp.stack([glorot(q, (H, H)) for q in ks[1:]]),
                    "b": jnp.zeros((H,))}
        if gnn == "gat":
            ks = jax.random.split(k, 4)
            return {"w": glorot(ks[0], (H, H)), "w_self": glorot(ks[1], (H, H)),
                    "a_src": glorot(ks[2], (H, 1))[:, 0],
                    "a_dst": glorot(ks[3], (H, 1))[:, 0],
                    "a_et": jnp.zeros((NUM_ETYPES,)), "b": jnp.zeros((H,))}
        if gnn == "sage":
            ks = jax.random.split(k, 2)
            return {"w_self": glorot(ks[0], (H, H)),
                    "w_nbr": glorot(ks[1], (H, H)), "b": jnp.zeros((H,))}
        raise ValueError(gnn)

    def init(key):
        keys = jax.random.split(key, L + len(mlp_dims) + 3)
        p = {"input": {"w": glorot(keys[0], (F, H)), "b": jnp.zeros((H,))},
             "type_emb": 0.02 * jax.random.normal(keys[1], (4, H)),
             "gnn": [layer(keys[2 + i]) for i in range(L - 1)],
             "last": layer(keys[1 + L]), "mlp": []}
        dims = (H + F,) + mlp_dims + (1,)
        for i in range(len(dims) - 1):
            p["mlp"].append({"w": glorot(keys[2 + L + i], (dims[i], dims[i + 1])),
                             "b": jnp.zeros((dims[i + 1],))})
        return p

    p = jax.jit(init)(jax.random.PRNGKey(int(seed)))
    return jax.tree.map(lambda a: np.asarray(a, np.float32), p)


# --------------------------------------------------------------- arithmetic
def _bf16(x):
    return x.astype(bfloat16).astype(np.float32)


def matmul(a, b, precision: str):
    """``a @ b`` in float32 with the operand rounding ``precision`` names."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    if precision == "highest":
        return a @ b
    ah, bh = _bf16(a), _bf16(b)
    if precision == "bf16":
        return ah @ bh
    if precision == "high":
        al, bl = _bf16(a - ah), _bf16(b - bh)
        return ah @ bh + (ah @ bl + al @ bh)
    raise ValueError(precision)


def _relu(x):
    return np.maximum(x, 0.0)


def _leaky(x):
    return np.where(x >= 0, x, np.float32(0.2) * x)


# ------------------------------------------------------------ DDS, stage 1
class Stage1Graph:
    """The stage-1 part of the DDS graph over a run's orders (arrival
    order).  Vertices: ``[0, n)`` shadows, then one per ``(entity, t)``
    pair in sorted order.  ``src/dst/etype`` list the kept in-edges."""

    def __init__(self, snapshot, entities, features, max_history: int,
                 max_deg: int):
        n, k = entities.shape
        snapshot = np.asarray(snapshot, np.int64)
        ent = entities.ravel()
        t = np.repeat(snapshot, k)
        pairs, pid = np.unique(np.stack([ent, t], 1), axis=0,
                               return_inverse=True)
        pid = pid.ravel()
        self.n_orders = n
        self.pairs = pairs
        self.features = np.asarray(features, np.float32)
        self.node_snapshot = np.concatenate([snapshot, pairs[:, 1]])
        ent_node = n + pid                                  # [n * k]
        shadow = np.repeat(np.arange(n), k)
        # canonical per-destination edge order: shadow edges in arrival
        # order, then the history self loop, then past snapshots ascending
        src = [shadow, ent_node]
        dst = [ent_node, shadow]
        et = [np.full(n * k, SHADOW_TO_ENTITY), np.full(n * k, ENTITY_TO_SHADOW)]
        seq = [np.arange(n * k), np.arange(n * k)]
        # entity history: pairs of one entity are adjacent and ascending
        first = np.r_[True, pairs[1:, 0] != pairs[:-1, 0]]
        start = np.maximum.accumulate(np.where(first, np.arange(len(pairs)), 0))
        rank = np.arange(len(pairs)) - start           # index among its snaps
        base = n * k
        src.append(n + np.arange(len(pairs)))
        dst.append(n + np.arange(len(pairs)))
        et.append(np.full(len(pairs), ENTITY_HIST))
        seq.append(base + np.zeros(len(pairs), np.int64))
        for back in range(1, max_history + 1):
            has = rank >= back
            cur = np.nonzero(has)[0]
            src.append(n + cur - back)
            dst.append(n + cur)
            et.append(np.full(len(cur), ENTITY_HIST))
            seq.append(base + 1 + (max_history - back) + np.zeros(len(cur),
                                                                  np.int64))
        src, dst = np.concatenate(src), np.concatenate(dst)
        et, seq = np.concatenate(et), np.concatenate(seq)
        # degree cap: most recent source snapshot first, canonical order next
        order = np.lexsort((seq, -self.node_snapshot[src], dst))
        src, dst, et = src[order], dst[order], et[order]
        head = np.r_[True, dst[1:] != dst[:-1]]
        gstart = np.maximum.accumulate(np.where(head, np.arange(len(dst)), 0))
        keep = (np.arange(len(dst)) - gstart) < max_deg
        self.src, self.dst, self.etype = src[keep], dst[keep], et[keep]
        self.num_nodes = n + len(pairs)
        self.node_type = np.r_[np.full(n, SHADOW), np.full(len(pairs), ENTITY)]

    def row_of(self, ent, t) -> np.ndarray:
        """Vertex ids of ``(entity, t)`` pairs (-1 where absent)."""
        key = np.stack([np.asarray(ent, np.int64), np.asarray(t, np.int64)], 1)
        i = np.searchsorted(self._pair_code(self.pairs), self._pair_code(key))
        i = np.minimum(i, len(self.pairs) - 1)
        ok = (self.pairs[i] == key).all(1)
        return np.where(ok, self.n_orders + i, -1)

    @staticmethod
    def _pair_code(p):
        return p[:, 0] * (1 << 20) + p[:, 1]


def _segment_sum(vals, seg, n):
    """Row sums of ``vals [E, H]`` grouped by ``seg [E]`` into ``[n, H]``."""
    out = np.zeros((n,) + vals.shape[1:], np.float32)
    if len(seg):
        order = np.argsort(seg, kind="stable")
        seg_s = seg[order]
        heads = np.r_[0, np.nonzero(seg_s[1:] != seg_s[:-1])[0] + 1]
        out[seg_s[heads]] = np.add.reduceat(vals[order], heads, axis=0)
    return out


def stage1(params, gnn_type: str, g: Stage1Graph, precision: str = "highest"):
    """Stage-1 hidden states ``[num_nodes, H]`` (shadows, then pairs)."""
    F = g.features.shape[1]
    x = np.zeros((g.num_nodes, F), np.float32)
    x[:g.n_orders] = g.features
    h = matmul(x, params["input"]["w"], precision) + params["input"]["b"]
    h = _relu(h + params["type_emb"][g.node_type])
    n = g.num_nodes
    for lp in params["gnn"]:
        if gnn_type in ("gcn", "sage"):
            out = matmul(h, lp["w_self"], precision)
            etypes = range(NUM_ETYPES) if gnn_type == "gcn" else [None]
            for e in etypes:
                sel = np.ones(len(g.src), bool) if e is None else g.etype == e
                s, d = g.src[sel], g.dst[sel]
                cnt = np.maximum(np.bincount(d, minlength=n), 1).astype(np.float32)
                agg = _segment_sum(h[s], d, n) / cnt[:, None]
                w = lp["w_nbr"] if e is None else lp["w_nbr"][e]
                out = out + matmul(agg, w, precision)
            h = _relu(out + lp["b"])
        else:  # gat
            z = matmul(h, lp["w"], precision)
            s_dst = matmul(z, lp["a_dst"][:, None], precision)[:, 0]
            s_src = matmul(z, lp["a_src"][:, None], precision)[:, 0]
            logit = _leaky(s_src[g.src] + s_dst[g.dst] + lp["a_et"][g.etype])
            mx = np.full(n, -np.inf, np.float32)
            np.maximum.at(mx, g.dst, logit)
            e = np.exp(logit - mx[g.dst])
            den = np.bincount(g.dst, weights=e, minlength=n).astype(np.float32)
            attn = (e / den[g.dst]).astype(np.float32)
            agg = _segment_sum(z[g.src] * attn[:, None], g.dst, n)
            h = _relu(agg + matmul(h, lp["w_self"], precision) + lp["b"])
    return h.astype(np.float32)


# ------------------------------------------------------------ keys, stage 2
def expected_keys(snapshot, entities, k_max: int):
    """Speed-layer keys per order: each linked entity's latest active
    snapshot strictly before the order's, in entity order, cold entities
    skipped.  Returns ``(ent [n, k_max], t [n, k_max], mask [n, k_max])``."""
    n, k = entities.shape
    last: dict = {}        # entity -> (latest snapshot, the one before it)
    ent_out = np.zeros((n, k_max), np.int64)
    t_out = np.zeros((n, k_max), np.int64)
    mask = np.zeros((n, k_max), np.float32)
    for i in range(n):
        t = int(snapshot[i])
        j = 0
        for e in entities[i].tolist():
            cur = last.get(e)
            if cur is not None:
                te = cur[0] if cur[0] < t else cur[1]
                if te is not None and j < k_max:
                    ent_out[i, j], t_out[i, j], mask[i, j] = e, te, 1.0
                    j += 1
        for e in entities[i].tolist():
            cur = last.get(e)
            if cur is None:
                last[e] = (t, None)
            elif cur[0] != t:
                last[e] = (t, cur[0])
    return ent_out, t_out, mask


def stage2_logits(params, gnn_type: str, emb, mask, feats,
                  precision: str = "highest"):
    """Online stage 2: ``emb [B, K, H]``, ``mask [B, K]``, ``feats [B, F]``
    -> logits ``[B]``."""
    B, K, H = emb.shape
    h = matmul(feats, params["input"]["w"], precision) + params["input"]["b"]
    h = _relu(h + params["type_emb"][ORDER])
    for lp in params["gnn"]:
        h = _relu(matmul(h, lp["w_self"], precision) + lp["b"])
    last = params["last"]
    if gnn_type in ("gcn", "sage"):
        w = mask / np.maximum(mask.sum(-1, keepdims=True), 1.0)
        agg = (emb * w[..., None]).sum(1)
        w_nbr = last["w_nbr"][ENTITY_TO_ORDER] if gnn_type == "gcn" \
            else last["w_nbr"]
        g = matmul(h, last["w_self"], precision) + matmul(agg, w_nbr, precision)
    else:
        z = matmul(emb.reshape(B * K, H), last["w"], precision).reshape(B, K, H)
        s_src = matmul(z.reshape(B * K, H), last["a_src"][:, None],
                       precision).reshape(B, K)
        s_dst = matmul(matmul(h, last["w"], precision), last["a_dst"][:, None],
                       precision)
        logit = _leaky(s_src + s_dst + last["a_et"][ENTITY_TO_ORDER])
        logit = np.where(mask > 0, logit, np.float32(-1e9))
        e = np.exp(logit - logit.max(-1, keepdims=True))
        attn = e / e.sum(-1, keepdims=True) * mask
        agg = (z * attn[..., None]).sum(1)
        g = agg + matmul(h, last["w_self"], precision)
    g = _relu(g + last["b"])
    x = np.concatenate([g, feats], axis=1)
    for i, lp in enumerate(params["mlp"]):
        x = matmul(x, lp["w"], precision) + lp["b"]
        if i + 1 < len(params["mlp"]):
            x = _relu(x)
    return x[:, 0].astype(np.float32)


def sigmoid(x):
    return (1.0 / (1.0 + np.exp(-np.asarray(x, np.float64)))).astype(np.float32)
