"""Edge-type-aware GNN layers on PaddedGraph (GCN / GAT / SAGE / HGT).

All layers consume the padded in-neighbor layout from ``core.graph`` and are
pure functions ``apply(params, h, graph) -> h'``.  The neighbor aggregation
is the paper's hot loop; it routes through ``kernels.ops.csr_spmm`` /
``kernels.ops.edge_softmax`` (Pallas, TPU) when ``use_pallas=True`` and
through the jnp reference path otherwise (CPU, dry-run lowering).  HGT's
stage-1 aggregation routes through ``kernels.ops.hgt_relation_project`` and
``kernels.ops.hgt_edge_softmax`` the same way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from repro.core.graph import EdgeType, NodeType, PaddedGraph


def _glorot(rng, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[-2], shape[-1]
    scale = jnp.sqrt(2.0 / (fan_in + fan_out))
    return jax.random.normal(rng, shape, dtype) * scale


# ---------------------------------------------------------------------------
# Aggregation primitives
# ---------------------------------------------------------------------------

def weighted_gather_sum(h, nbr_idx, weights, use_pallas: bool = False):
    """out[i] = sum_d weights[i, d] * h[nbr_idx[i, d]]  — the SpMM core.

    h: [N, H]; nbr_idx: [N, D] int32; weights: [N, D] float.
    """
    if use_pallas:
        from repro.kernels.ops import csr_spmm

        return csr_spmm(h, nbr_idx, weights)
    msgs = jnp.take(h, nbr_idx, axis=0)  # [N, D, H]
    return jnp.einsum("ndh,nd->nh", msgs, weights.astype(h.dtype))


def per_etype_mean(h, graph: PaddedGraph, use_pallas: bool = False):
    """Mean-aggregate neighbor states separately per edge type.

    Returns [NUM_ETYPES, N, H]."""
    outs = []
    for e in range(EdgeType.NUM):
        w = graph.nbr_mask * (graph.nbr_etype == e)
        cnt = jnp.maximum(w.sum(-1, keepdims=True), 1.0)
        outs.append(weighted_gather_sum(h, graph.nbr_idx, w / cnt, use_pallas))
    return jnp.stack(outs)


# ---------------------------------------------------------------------------
# GCN
# ---------------------------------------------------------------------------

def gcn_init(rng, in_dim: int, out_dim: int):
    ks = jax.random.split(rng, EdgeType.NUM + 1)
    return {
        "w_self": _glorot(ks[0], (in_dim, out_dim)),
        "w_nbr": jnp.stack([_glorot(k, (in_dim, out_dim)) for k in ks[1:]]),  # [E, in, out]
        "b": jnp.zeros((out_dim,)),
    }


def gcn_apply(params, h, graph: PaddedGraph, use_pallas: bool = False):
    agg = per_etype_mean(h, graph, use_pallas)          # [E, N, in]
    out = h @ params["w_self"]
    out = out + jnp.einsum("enh,eho->no", agg, params["w_nbr"])
    return jax.nn.relu(out + params["b"])


# ---------------------------------------------------------------------------
# GAT (single-head GATv1 with edge-type bias, masked neighbor softmax)
# ---------------------------------------------------------------------------

def gat_init(rng, in_dim: int, out_dim: int):
    ks = jax.random.split(rng, 4)
    return {
        "w": _glorot(ks[0], (in_dim, out_dim)),
        "w_self": _glorot(ks[1], (in_dim, out_dim)),
        "a_src": _glorot(ks[2], (out_dim, 1))[:, 0],
        "a_dst": _glorot(ks[3], (out_dim, 1))[:, 0],
        "a_et": jnp.zeros((EdgeType.NUM,)),
        "b": jnp.zeros((out_dim,)),
    }


def gat_apply(params, h, graph: PaddedGraph, use_pallas: bool = False):
    z = h @ params["w"]                                  # [N, H]
    s_dst = z @ params["a_dst"]                          # [N]
    s_src = z @ params["a_src"]                          # [N]
    if use_pallas:
        from repro.kernels.ops import edge_softmax_agg

        agg = edge_softmax_agg(
            z, s_src, s_dst, graph.nbr_idx, graph.nbr_mask,
            params["a_et"][graph.nbr_etype],
        )
    else:
        logits = (
            jnp.take(s_src, graph.nbr_idx, axis=0)
            + s_dst[:, None]
            + params["a_et"][graph.nbr_etype]
        )
        logits = jax.nn.leaky_relu(logits, 0.2)
        logits = jnp.where(graph.nbr_mask > 0, logits, -1e9)
        attn = jax.nn.softmax(logits, axis=-1) * graph.nbr_mask
        msgs = jnp.take(z, graph.nbr_idx, axis=0)        # [N, D, H]
        agg = jnp.einsum("ndh,nd->nh", msgs, attn)
    out = agg + h @ params["w_self"]
    return jax.nn.relu(out + params["b"])


# ---------------------------------------------------------------------------
# GraphSAGE (mean aggregator) — extra baseline beyond the paper's GCN/GAT
# ---------------------------------------------------------------------------

def sage_init(rng, in_dim: int, out_dim: int):
    ks = jax.random.split(rng, 2)
    return {
        "w_self": _glorot(ks[0], (in_dim, out_dim)),
        "w_nbr": _glorot(ks[1], (in_dim, out_dim)),
        "b": jnp.zeros((out_dim,)),
    }


def sage_apply(params, h, graph: PaddedGraph, use_pallas: bool = False):
    w = graph.nbr_mask
    cnt = jnp.maximum(w.sum(-1, keepdims=True), 1.0)
    agg = weighted_gather_sum(h, graph.nbr_idx, w / cnt, use_pallas)
    out = h @ params["w_self"] + agg @ params["w_nbr"]
    return jax.nn.relu(out + params["b"])


# ---------------------------------------------------------------------------
# HGT (Hu, Dong, Wang, Sun, WWW 2020, arXiv 2003.01332): typed multi-head
# attention with relative temporal encoding, in the update form of the
# authors' ``pyHGT/conv.py::HGTConv`` (gated skip and a LayerNorm per type)
# ---------------------------------------------------------------------------

#: node types with their own K/Q/M/A linears, skip gate and LayerNorm
#: (ORDER, SHADOW, ENTITY); PAD rows are masked
HGT_NODE_TYPES = 3
#: the source node type each relation fixes, by ``EdgeType`` code: in DDS
#: every relation fixes both end types, so mu per (relation, head) is the
#: paper's mu per <type(s), relation, type(t)> triplet
HGT_SRC_TYPE = (NodeType.SHADOW, NodeType.ENTITY, NodeType.ENTITY,
                NodeType.ENTITY)
#: stage 1's relations: the final hop is the speed layer's alone
HGT_STAGE1_RELATIONS = (EdgeType.SHADOW_TO_ENTITY, EdgeType.ENTITY_TO_SHADOW,
                        EdgeType.ENTITY_HIST)
#: time gaps the kernel path reads from a table; a graph with a gap outside
#: [0, RTE_TABLE) takes the exact per-edge path instead
RTE_TABLE = 64
LN_EPS = 1e-5


def hgt_init(rng, in_dim: int, out_dim: int, num_heads: int = 1):
    """Glorot linears, mu = 1, skip = 1, LayerNorm gamma = 1 and beta = 0."""
    if in_dim != out_dim or out_dim % num_heads:
        raise ValueError("HGT needs in_dim == out_dim, divisible by num_heads")
    T, R, H = HGT_NODE_TYPES, EdgeType.NUM, out_dim
    dk = H // num_heads
    ks = jax.random.split(rng, 7)

    def typed(k):
        return {"w": _glorot(k, (T, H, H)), "b": jnp.zeros((T, H))}

    return {
        "k": typed(ks[0]), "q": typed(ks[1]), "m": typed(ks[2]),
        "a": typed(ks[3]),
        "att": _glorot(ks[4], (R, num_heads, dk, dk)),
        "msg": _glorot(ks[5], (R, num_heads, dk, dk)),
        "mu": jnp.ones((R, num_heads)),
        "rte": {"w": _glorot(ks[6], (H, H)), "b": jnp.zeros((H,))},
        "skip": jnp.ones((T,)),
        "ln_g": jnp.ones((T, H)), "ln_b": jnp.zeros((T, H)),
    }


def rte_base(dt, dim: int):
    """HGT Eq. 6: ``Base(dt)_{2j} = sin(dt / 10000^(2j/d))``, ``_{2j+1} =
    cos(...)``; ``dt`` int ``[...]`` -> ``[..., dim]`` float32."""
    inv = np.float32(1.0) / np.power(
        np.float32(10000.0), np.arange(0, dim, 2, dtype=np.float32)
        / np.float32(dim))
    ang = jnp.asarray(dt, jnp.float32)[..., None] * inv
    return jnp.stack([jnp.sin(ang), jnp.cos(ang)], -1).reshape(
        ang.shape[:-1] + (dim,))


def rte(params, dt):
    """HGT Eq. 7's ``T-Linear(Base(dt))``, the term added to a source row."""
    p = params["rte"]
    return rte_base(dt, p["w"].shape[0]) @ p["w"] + p["b"]


def erf(x):
    """float32 erf as a rational approximation on [-4, 4] (the one XLA
    uses for float32), written out so the Pallas kernels, which have no
    erf, compute exactly what the XLA paths compute."""
    alpha = (-2.72614225801306e-10, 2.77068142495902e-08,
             -2.10102402082508e-06, -5.69250639462346e-05,
             -7.34990630326855e-04, -2.95459980854025e-03,
             -1.60960333262415e-02)
    beta = (-1.45660718464996e-05, -2.13374055278905e-04,
            -1.68282697438203e-03, -7.37332916720468e-03,
            -1.42647390514189e-02)
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    p = jnp.zeros_like(x) + alpha[0]
    for c in alpha[1:]:
        p = p * x2 + c
    q = jnp.zeros_like(x) + beta[0]
    for c in beta[1:]:
        q = q * x2 + c
    return x * p / q


def gelu(x):
    """Exact (erf) GELU, as PyTorch's ``F.gelu`` in the authors' code."""
    return 0.5 * x * (1.0 + erf(x * np.float32(0.7071067811865476)))


def layer_norm(x, g, b):
    mu = x.mean(-1, keepdims=True)
    xc = x - mu
    var = (xc * xc).mean(-1, keepdims=True)
    return xc * jax.lax.rsqrt(var + LN_EPS) * g + b


def hgt_typed_linear(p, x, node_type):
    """``x @ W[type] + b[type]`` per row: ``node_type`` an int (one type for
    every row) or ``[N]`` codes (PAD and other codes give 0)."""
    if isinstance(node_type, int):
        return x @ p["w"][node_type] + p["b"][node_type]
    out = jnp.zeros(x.shape[:-1] + (p["w"].shape[-1],), x.dtype)
    for t in range(HGT_NODE_TYPES):
        out = jnp.where((node_type == t)[..., None],
                        x @ p["w"][t] + p["b"][t], out)
    return out


def hgt_num_heads(params) -> int:
    return params["mu"].shape[1]


def _heads(x, nh: int):
    return x.reshape(x.shape[:-1] + (nh, x.shape[-1] // nh))


def _relation_kv(params, hs, r: int):
    """Keys and messages ``[..., heads, d_k]`` of source rows ``hs`` (RTE
    already added) under relation ``r``: the source type's K/M linear, then
    W^ATT / W^MSG of (r, head)."""
    nh, tau = hgt_num_heads(params), HGT_SRC_TYPE[r]
    k = _heads(hgt_typed_linear(params["k"], hs, tau), nh)
    m = _heads(hgt_typed_linear(params["m"], hs, tau), nh)
    return (jnp.einsum("...hc,hce->...he", k, params["att"][r]),
            jnp.einsum("...hc,hce->...he", m, params["msg"][r]))


def _masked_softmax(logits, mask, axis):
    """Softmax over ``axis`` of the unmasked entries, 0 where masked (and
    everywhere for a row with no unmasked entry)."""
    logits = jnp.where(mask > 0, logits, -1e30)
    e = jnp.exp(logits - logits.max(axis, keepdims=True))
    return e / e.sum(axis, keepdims=True) * mask


def hgt_edge_dt(graph: PaddedGraph):
    """Per in-edge time gap ``snapshot(t) - snapshot(s)`` ``[N, D]``, 0 on
    padding slots."""
    dt = graph.snapshot[:, None] - jnp.take(graph.snapshot, graph.nbr_idx,
                                            axis=0)
    return jnp.where(graph.nbr_mask > 0, dt, 0)


def _hgt_attend_edges(params, h, graph: PaddedGraph, relations, mask, dt):
    """Per-edge HGT aggregation in plain jnp: exact for any time gap."""
    nh = hgt_num_heads(params)
    dk = h.shape[-1] // nh
    hs = jnp.take(h, graph.nbr_idx, axis=0) + rte(params, dt)   # [N, D, H]
    shape = graph.nbr_idx.shape + (nh, dk)
    k_e = jnp.zeros(shape, h.dtype)
    m_e = jnp.zeros(shape, h.dtype)
    for r in relations:
        k_r, m_r = _relation_kv(params, hs, r)
        sel = (graph.nbr_etype == r)[..., None, None]
        k_e = jnp.where(sel, k_r, k_e)
        m_e = jnp.where(sel, m_r, m_e)
    q = _heads(hgt_typed_linear(params["q"], h, graph.node_type), nh)
    mu = params["mu"][graph.nbr_etype] / np.float32(np.sqrt(dk))  # [N, D, nh]
    logits = jnp.einsum("ndhc,nhc->ndh", k_e, q) * mu
    attn = _masked_softmax(logits, mask[..., None], axis=1)
    return jnp.einsum("ndh,ndhc->nhc", attn, m_e).reshape(h.shape)


def _block_diag(w):
    """``[heads, d, d]`` per-head matrices -> one ``[H, H]`` block-diagonal."""
    nh, d, _ = w.shape
    return jnp.einsum("hce,hg->hcge", w, jnp.eye(nh, dtype=w.dtype)).reshape(
        nh * d, nh * d)


def hgt_folded_kv(params, relations):
    """Per relation, the source type's K and M linears with the relation's
    per-head W^ATT / W^MSG folded in: ``(wk, bk, wm, bm)``, weights
    ``[R, H, H]`` and biases ``[R, H]``, so one matmul per row gives a
    relation's keys (or messages) for every head."""
    out = []
    for lin, rel in (("k", "att"), ("m", "msg")):
        bd = [_block_diag(params[rel][r]) for r in relations]
        src = [HGT_SRC_TYPE[r] for r in relations]
        out.append(jnp.stack([params[lin]["w"][t] @ d for t, d in zip(src, bd)]))
        out.append(jnp.stack([params[lin]["b"][t] @ d for t, d in zip(src, bd)]))
    return tuple(out)


def _hgt_attend_kernels(params, h, graph: PaddedGraph, relations, mask, dt):
    """The kernel path: each relation's keys and messages projected once
    per node (``hgt_relation_project``: the source type's K/M linear with
    W^ATT / W^MSG folded in), the RTE term from a table over the gaps
    ``[0, RTE_TABLE)`` pushed through the same folded weights, both rows
    gathered per edge by ``relation * N + src`` and ``relation * T + dt``,
    then the per-head masked softmax and the weighted sum
    (``hgt_edge_softmax``)."""
    from repro.kernels.ops import hgt_edge_softmax, hgt_relation_project

    n, hd = h.shape
    nh = hgt_num_heads(params)
    dk = hd // nh
    rels = tuple(relations)
    wk, bk, wm, bm = hgt_folded_kv(params, rels)
    proj = hgt_relation_project(h, jnp.concatenate([wk, wm]),
                                jnp.concatenate([bk, bm]))   # [2R, N, H]
    nr = len(rels)
    table = rte(params, jnp.arange(RTE_TABLE))                  # [T, H]
    tk = jnp.einsum("th,rho->rto", table, wk).reshape(nr * RTE_TABLE, hd)
    tm = jnp.einsum("th,rho->rto", table, wm).reshape(nr * RTE_TABLE, hd)
    slot = np.zeros(EdgeType.NUM, np.int32)
    slot[list(rels)] = np.arange(nr)
    rs = jnp.asarray(slot)[graph.nbr_etype]                     # [N, D]
    src_row = rs * n + graph.nbr_idx
    dt_row = rs * RTE_TABLE + dt
    ke = (jnp.take(proj[:nr].reshape(nr * n, hd), src_row, axis=0)
          + jnp.take(tk, dt_row, axis=0))
    me = (jnp.take(proj[nr:].reshape(nr * n, hd), src_row, axis=0)
          + jnp.take(tm, dt_row, axis=0))
    q = hgt_typed_linear(params["q"], h, graph.node_type)
    scale = params["mu"][graph.nbr_etype] / np.float32(np.sqrt(dk))
    return hgt_edge_softmax(q, ke, me, scale * mask[..., None], mask)


def hgt_attend(params, h, graph: PaddedGraph, use_pallas: bool = False,
               relations=tuple(range(EdgeType.NUM))):
    """HGT's aggregate ``H~[t] = ||_i sum_s softmax(a^i) M^i(s)`` over the
    in-edges of the given relations (0 for a node with none), ``[N, H]``.
    Eq. 3-6: keys from the RTE-shifted source rows by the source type's
    K-Linear, queries by the target type's Q-Linear, logits through
    W^ATT and mu / sqrt(d_k), messages by M-Linear and W^MSG."""
    rel_mask = jnp.zeros_like(graph.nbr_mask)
    for r in relations:
        rel_mask = rel_mask + (graph.nbr_etype == r)
    mask = graph.nbr_mask * rel_mask
    dt = hgt_edge_dt(graph)
    if not use_pallas:
        return _hgt_attend_edges(params, h, graph, relations, mask, dt)
    in_table = jnp.all((mask == 0) | ((dt >= 0) & (dt < RTE_TABLE)))
    return jax.lax.cond(
        in_table,
        lambda: _hgt_attend_kernels(params, h, graph, relations, mask,
                                    jnp.clip(dt, 0, RTE_TABLE - 1)),
        lambda: _hgt_attend_edges(params, h, graph, relations, mask, dt))


def hgt_update(params, agg, h, node_type):
    """``H'[t] = LN_type(alpha * A-Linear_type(GELU(H~[t])) + (1 - alpha) h)``
    with ``alpha = sigmoid(skip_type)``; ``node_type`` an int or ``[N]``
    codes (PAD rows give 0)."""
    a = hgt_typed_linear(params["a"], gelu(agg), node_type)
    if isinstance(node_type, int):
        alpha = jax.nn.sigmoid(params["skip"][node_type])
        return layer_norm(alpha * a + (1.0 - alpha) * h,
                          params["ln_g"][node_type], params["ln_b"][node_type])
    t = jnp.clip(node_type, 0, HGT_NODE_TYPES - 1)
    alpha = jax.nn.sigmoid(params["skip"])[t][:, None]
    out = layer_norm(alpha * a + (1.0 - alpha) * h, params["ln_g"][t],
                     params["ln_b"][t])
    return jnp.where((node_type < HGT_NODE_TYPES)[:, None], out, 0.0)


def hgt_apply(params, h, graph: PaddedGraph, use_pallas: bool = False,
              relations=tuple(range(EdgeType.NUM))):
    agg = hgt_attend(params, h, graph, use_pallas, relations)
    return hgt_update(params, agg, h, graph.node_type)


def hgt_slot_attend(params, order_h, emb, mask, slot_dt):
    """The final hop online: the order's ``H~`` over its ``K`` KV slots
    (relation ENTITY_TO_ORDER, ``slot_dt [B, K]`` the order's snapshot minus
    the served key's), ``[B, H]``."""
    nh = hgt_num_heads(params)
    dk = order_h.shape[-1] // nh
    r = EdgeType.ENTITY_TO_ORDER
    k, m = _relation_kv(params, emb + rte(params, slot_dt), r)  # [B, K, nh, dk]
    q = _heads(hgt_typed_linear(params["q"], order_h, NodeType.ORDER), nh)
    logits = jnp.einsum("bkhc,bhc->bkh", k, q) * (
        params["mu"][r] / np.float32(np.sqrt(dk)))
    attn = _masked_softmax(logits, mask[..., None], axis=1)
    return jnp.einsum("bkh,bkhc->bhc", attn, m).reshape(order_h.shape)


LAYER_REGISTRY = {
    "gcn": (gcn_init, gcn_apply),
    "gat": (gat_init, gat_apply),
    "sage": (sage_init, sage_apply),
    "hgt": (hgt_init, hgt_apply),
}
