"""Plain reference of the LNN fraud scorer with an HGT as its GNN, in numpy.

HGT: Hu, Dong, Wang, Sun, "Heterogeneous Graph Transformer", WWW 2020,
arXiv 2003.01332, section 3 (Eq. 1-7), in the update form of the authors'
``pyHGT/conv.py::HGTConv``.  For head ``i``, source ``s``, relation ``e``
and target ``t``, ``tau`` a node's type and ``phi`` an edge's relation:

* RTE (Eq. 6-7): ``H^[s] = H[s] + T-Linear(Base(dT))``, ``dT = snapshot(t)
  - snapshot(s)``, ``Base(dT)_{2j} = sin(dT / 10000^(2j/d))``,
  ``Base(dT)_{2j+1} = cos(...)``;
* ``K^i(s) = K-Linear^i_tau(s)(H^[s])``, ``Q^i(t) = Q-Linear^i_tau(t)(H[t])``,
  ``a^i = K^i(s) W^ATT_{phi,i} Q^i(t)^T mu_{phi,i} / sqrt(d_k)``, a softmax
  over the in-edges of ``t`` per head;
* ``M^i(s) = M-Linear^i_tau(s)(H^[s]) W^MSG_{phi,i}``,
  ``H~[t] = ||_i sum_s softmax(a^i) M^i(s)`` (0 for a node with none);
* ``H'[t] = LN_tau(t)(alpha A-Linear_tau(t)(GELU(H~[t])) + (1 - alpha) H[t])``,
  ``alpha = sigmoid(skip_tau(t))`` (the paper's Eq. 5 has neither the gate
  nor the LayerNorm; the authors' code has both).

It imports nothing of the program and takes nothing the program made.  It
reuses ``lnn_reference.py`` (loaded by path) for the DDS graph, the keys,
the matrix product at each precision and the segment sums; the HGT layers
are edge-list sums, O(E * H^2) for the keys and messages, no padding.

The comparison's interface (``correctness.Expected``) hands stage 2 the
slots' rows, mask and features, not the orders' snapshots that each slot's
gap needs.  ``expected_keys`` therefore records each order's snapshot under
its key rows (a key row fixes the order's snapshot: a later order of the
same warm entities reads later keys), ``Stage1Graph.row_of`` records the
key rows it was last asked for, and ``stage2_logits`` reads the gaps from
both: ``Expected.probs`` always asks for the rows right before the logits.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np
from scipy.special import erf

_spec = importlib.util.spec_from_file_location(
    "onchip_ref_lnn_reference_base", Path(__file__).with_name("lnn_reference.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

ORDER, SHADOW, ENTITY = _base.ORDER, _base.SHADOW, _base.ENTITY
ENTITY_TO_ORDER, NUM_ETYPES = _base.ENTITY_TO_ORDER, _base.NUM_ETYPES
NODE_TYPES = 3
#: the source type each relation fixes (shadow->entity, entity->shadow,
#: entity history, entity->order)
SRC_TYPE = (SHADOW, ENTITY, ENTITY, ENTITY)
LN_EPS = np.float32(1e-5)

matmul = _base.matmul
sigmoid = _base.sigmoid
_segment_sum = _base._segment_sum

_order_t: dict = {}      # key row -> the order's snapshot (expected_keys)
_asked: list = []        # the key rows row_of was last asked for


# ------------------------------------------------------------------ weights
def init_params(seed: int, model: dict) -> dict:
    """The weights an ``lnn_init(jax.random.PRNGKey(seed), cfg)`` draws for
    ``gnn_type="hgt"``, as float32 numpy arrays, drawn on JAX's default
    device in one jitted call (see ``lnn_reference.init_params``)."""
    import jax
    import jax.numpy as jnp

    L, H = model["num_gnn_layers"], model["hidden_dim"]
    F, mlp_dims = model["feat_dim"], tuple(model["mlp_dims"])
    nh = model["num_heads"]
    dk = H // nh

    def glorot(k, shape):
        return jax.random.normal(k, shape, jnp.float32) * jnp.sqrt(
            2.0 / (shape[-2] + shape[-1]))

    def layer(k):
        ks = jax.random.split(k, 7)

        def typed(q):
            return {"w": glorot(q, (NODE_TYPES, H, H)),
                    "b": jnp.zeros((NODE_TYPES, H))}

        return {"k": typed(ks[0]), "q": typed(ks[1]), "m": typed(ks[2]),
                "a": typed(ks[3]),
                "att": glorot(ks[4], (NUM_ETYPES, nh, dk, dk)),
                "msg": glorot(ks[5], (NUM_ETYPES, nh, dk, dk)),
                "mu": jnp.ones((NUM_ETYPES, nh)),
                "rte": {"w": glorot(ks[6], (H, H)), "b": jnp.zeros((H,))},
                "skip": jnp.ones((NODE_TYPES,)),
                "ln_g": jnp.ones((NODE_TYPES, H)),
                "ln_b": jnp.zeros((NODE_TYPES, H))}

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


# --------------------------------------------------------------- the layer
def _gelu(x):
    x64 = x.astype(np.float64)
    return (0.5 * x64 * (1.0 + erf(x64 / np.sqrt(2.0)))).astype(np.float32)


def _layer_norm(x, g, b):
    mu = x.mean(-1, keepdims=True, dtype=np.float32)
    xc = x - mu
    var = (xc * xc).mean(-1, keepdims=True, dtype=np.float32)
    return (xc / np.sqrt(var + LN_EPS) * g + b).astype(np.float32)


def _rte(lp, dt, precision):
    """``T-Linear(Base(dt))`` rows for int gaps ``dt [E]``, computed once
    per distinct gap (the rows of equal gaps are the same numbers)."""
    H = lp["rte"]["w"].shape[0]
    gaps, which = np.unique(np.asarray(dt, np.int64), return_inverse=True)
    inv = np.float32(1.0) / np.power(
        np.float32(10000.0), np.arange(0, H, 2, dtype=np.float32) / np.float32(H))
    ang = gaps.astype(np.float32)[:, None] * inv
    base = np.stack([np.sin(ang), np.cos(ang)], -1).reshape(len(gaps), H)
    rows = matmul(base, lp["rte"]["w"], precision) + lp["rte"]["b"]
    return rows[which.ravel()]


def _typed(p, x, types, precision):
    """``x @ W[type] + b[type]`` per row."""
    out = np.zeros((x.shape[0], p["w"].shape[-1]), np.float32)
    for t in np.unique(types):
        rows = types == t
        out[rows] = matmul(x[rows], p["w"][t], precision) + p["b"][t]
    return out


def _per_head(x, w, precision):
    """``x [E, H]`` times each head's ``w [nh, dk, dk]`` -> ``[E, nh, dk]``."""
    nh, dk, _ = w.shape
    return np.stack([matmul(x[:, i * dk:(i + 1) * dk], w[i], precision)
                     for i in range(nh)], 1)


def _keys_messages(lp, hs, etype, precision):
    nh = lp["mu"].shape[1]
    dk = hs.shape[1] // nh
    k = np.zeros((len(hs), nh, dk), np.float32)
    m = np.zeros((len(hs), nh, dk), np.float32)
    for r in np.unique(etype):
        sel = etype == r
        tau = SRC_TYPE[r]
        kr = matmul(hs[sel], lp["k"]["w"][tau], precision) + lp["k"]["b"][tau]
        mr = matmul(hs[sel], lp["m"]["w"][tau], precision) + lp["m"]["b"][tau]
        k[sel] = _per_head(kr, lp["att"][r], precision)
        m[sel] = _per_head(mr, lp["msg"][r], precision)
    return k, m


def _update(lp, agg, h, types, precision):
    a = _typed(lp["a"], _gelu(agg), types, precision)
    alpha = (1.0 / (1.0 + np.exp(-lp["skip"].astype(np.float64)))).astype(
        np.float32)[types][:, None]
    return _layer_norm(alpha * a + (1.0 - alpha) * h, lp["ln_g"][types],
                       lp["ln_b"][types])


def hgt_layer(lp, h, src, dst, etype, node_type, dt, precision):
    """One HGT layer over the edge list ``src -> dst`` (relations
    ``etype``, gaps ``dt``) of ``n = len(h)`` nodes."""
    n, H = h.shape
    nh = lp["mu"].shape[1]
    dk = H // nh
    hs = h[src] + _rte(lp, dt, precision)
    k, m = _keys_messages(lp, hs, etype, precision)
    q = _typed(lp["q"], h, node_type, precision).reshape(n, nh, dk)
    logit = (k * q[dst]).sum(-1) * (lp["mu"][etype] / np.float32(np.sqrt(dk)))
    mx = np.full((n, nh), -np.inf, np.float32)
    np.maximum.at(mx, dst, logit)
    e = np.exp(logit - mx[dst])
    den = _segment_sum(e, dst, n)
    attn = (e / den[dst]).astype(np.float32)
    agg = _segment_sum((attn[:, :, None] * m).reshape(len(src), H), dst, n)
    return _update(lp, agg, h, node_type, precision)


# ------------------------------------------------------------ DDS, stage 1
class Stage1Graph(_base.Stage1Graph):
    """``lnn_reference.Stage1Graph``; ``row_of`` also records the key rows
    it was asked for (see the module's docstring)."""

    def row_of(self, ent, t) -> np.ndarray:
        _asked[:] = [np.asarray(ent, np.int64), np.asarray(t, np.int64)]
        return super().row_of(ent, t)


def stage1(params, gnn_type: str, g: Stage1Graph, precision: str = "highest"):
    """Stage-1 hidden states ``[num_nodes, H]`` (shadows, then pairs)."""
    if gnn_type != "hgt":
        raise ValueError(f"this reference is the hgt LNN's, not {gnn_type}")
    F = g.features.shape[1]
    x = np.zeros((g.num_nodes, F), np.float32)
    x[:g.n_orders] = g.features
    h = matmul(x, params["input"]["w"], precision) + params["input"]["b"]
    h = np.maximum(h + params["type_emb"][g.node_type], 0.0)
    dt = g.node_snapshot[g.dst] - g.node_snapshot[g.src]
    for lp in params["gnn"]:
        h = hgt_layer(lp, h, g.src, g.dst, g.etype, g.node_type, dt, precision)
    return h.astype(np.float32)


# ------------------------------------------------------------ keys, stage 2
def _row_code(ent, t) -> bytes:
    return np.concatenate([ent, t]).astype(np.int64).tobytes()


def expected_keys(snapshot, entities, k_max: int):
    """``lnn_reference.expected_keys``; records each order's snapshot under
    its key rows."""
    ent, t, mask = _base.expected_keys(snapshot, entities, k_max)
    _order_t.clear()
    for i in range(len(ent)):
        _order_t[_row_code(ent[i], t[i])] = int(snapshot[i])
    return ent, t, mask


def _slot_dt(n: int, k: int, mask):
    """Each slot's gap, the order's snapshot minus its key's, from the key
    rows last asked of ``row_of``."""
    ent, t = (a.reshape(n, k) for a in _asked)
    order_t = np.asarray([_order_t.get(_row_code(ent[i], t[i]), 0)
                          for i in range(n)], np.int64)
    return np.where(mask > 0, order_t[:, None] - t, 0)


def stage2_logits(params, gnn_type: str, emb, mask, feats,
                  precision: str = "highest"):
    """Online stage 2: ``emb [B, K, H]``, ``mask [B, K]``, ``feats [B, F]``
    -> logits ``[B]``; the target of the last layer is the order."""
    B, K, H = emb.shape
    h = matmul(feats, params["input"]["w"], precision) + params["input"]["b"]
    h = np.maximum(h + params["type_emb"][ORDER], 0.0)
    order = np.full(B, ORDER)
    for lp in params["gnn"]:
        # no stage-1 in-edge reaches an order: H~ = 0
        h = _update(lp, np.zeros_like(h), h, order, precision)
    lp = params["last"]
    nh = lp["mu"].shape[1]
    dk = H // nh
    dt = _slot_dt(B, K, mask).reshape(-1)
    hs = emb.reshape(B * K, H) + _rte(lp, dt, precision)
    k, m = _keys_messages(lp, hs, np.full(B * K, ENTITY_TO_ORDER), precision)
    q = _typed(lp["q"], h, order, precision).reshape(B, 1, nh, dk)
    logit = (k.reshape(B, K, nh, dk) * q).sum(-1) * (
        lp["mu"][ENTITY_TO_ORDER] / np.float32(np.sqrt(dk)))
    valid = mask[..., None] > 0
    logit = np.where(valid, logit, np.float32(-1e30))
    e = np.exp(logit - logit.max(1, keepdims=True))
    attn = e / e.sum(1, keepdims=True) * valid
    agg = (attn[..., None] * m.reshape(B, K, nh, dk)).sum(1).reshape(B, H)
    g = _update(lp, agg.astype(np.float32), h, order, precision)
    x = np.concatenate([g, feats], axis=1)
    for i, mp in enumerate(params["mlp"]):
        x = matmul(x, mp["w"], precision) + mp["b"]
        if i + 1 < len(params["mlp"]):
            x = np.maximum(x, 0.0)
    return x[:, 0].astype(np.float32)
