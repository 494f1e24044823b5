"""Lambda Neural Network (LNN) — paper §3.3.

A deep GNN split in two stages at the ``entity_{t-e}`` cut:

* **stage 1** (batch layer): input projection + all GNN layers except the
  last, run over the whole DDS community graph.  Its output rows for entity
  vertices are the embeddings that production would periodically refresh and
  push to a key-value store.
* **stage 2** (speed layer): the final GNN layer restricted to the
  ``entity_{t-e} -> order_t`` final-hop edges, concatenated with the raw
  order features, followed by an MLP scorer — exactly the computation an
  online checkout approval performs after KV lookups.

``lnn_forward = stage2 ∘ stage1`` end-to-end for training; the split is
exact because effective orders have *only* final-hop in-edges in a DDS graph
(verified by ``core.dds.check_no_future_leak`` and the stage-equivalence
test).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core.graph import EdgeType, NodeType, PaddedGraph
from repro.core.layers import (
    HGT_STAGE1_RELATIONS,
    LAYER_REGISTRY,
    _glorot,
    hgt_attend,
    hgt_slot_attend,
    hgt_update,
    weighted_gather_sum,
)


@dataclass(frozen=True)
class LNNConfig:
    """Hyperparameters of the Lambda Neural Network (see module docstring).

    ``entity_types`` opts into heterogeneous per-type entity towers: a
    non-empty tuple of type names (canonically
    :data:`repro.core.hetero.ENTITY_TYPE_NAMES`) adds a per-type input
    embedding to stage 1 and per-type weight blocks to stage 2.  Empty
    (the default) keeps the homogeneous model — parameters, pytree
    structure, and numerics all bit-identical to the pre-hetero layout.
    """

    gnn_type: str = "gcn"            # 'gcn' | 'gat' | 'sage' | 'hgt'
    num_gnn_layers: int = 3          # total GNN layers (>= 2: stage1 has L-1)
    hidden_dim: int = 64
    mlp_dims: tuple = (64, 32)
    feat_dim: int = 16               # raw checkout feature width
    use_pallas: bool = False
    pos_weight: float = 1.0          # BCE positive-class weight (fraud is rare)
    entity_types: tuple = ()         # () = homogeneous; e.g. hetero.ENTITY_TYPE_NAMES
    num_heads: int = 1               # attention heads (hgt); divides hidden_dim

    def __post_init__(self):
        if self.num_gnn_layers < 2:
            raise ValueError("LNN needs >= 2 GNN layers (stage1 >= 1, stage2 == 1)")
        if self.gnn_type not in LAYER_REGISTRY:
            raise ValueError(f"unknown gnn_type {self.gnn_type}")
        if self.gnn_type == "hgt" and self.entity_types:
            raise ValueError("hgt types its own linears; entity_types is "
                             "for gcn, gat and sage")
        if self.num_heads < 1 or self.hidden_dim % self.num_heads:
            raise ValueError(f"num_heads {self.num_heads} must divide "
                             f"hidden_dim {self.hidden_dim}")
        object.__setattr__(self, "entity_types", tuple(self.entity_types))


def lnn_init(rng, cfg: LNNConfig):
    """Initialize an LNN parameter pytree for ``cfg``.

    The homogeneous layout (and its PRNG key schedule) is untouched by the
    heterogeneous extension: typed parameters draw from *extra* keys
    appended after the base split, and the ``"typed"`` subtree exists only
    when ``cfg.entity_types`` is non-empty.
    """
    init_fn, _ = LAYER_REGISTRY[cfg.gnn_type]
    if cfg.gnn_type == "hgt":
        init_fn = functools.partial(init_fn, num_heads=cfg.num_heads)
    n_base = cfg.num_gnn_layers + len(cfg.mlp_dims) + 3
    n_types = len(cfg.entity_types)
    keys = jax.random.split(rng, n_base)
    params = {
        "input": {
            "w": _glorot(keys[0], (cfg.feat_dim, cfg.hidden_dim)),
            "b": jnp.zeros((cfg.hidden_dim,)),
        },
        # small learned embedding per node type so entities (zero features)
        # are distinguishable from shadows at the input
        "type_emb": 0.02 * jax.random.normal(keys[1], (4, cfg.hidden_dim)),
        "gnn": [
            init_fn(keys[2 + i], cfg.hidden_dim, cfg.hidden_dim)
            for i in range(cfg.num_gnn_layers - 1)
        ],
        "last": init_fn(keys[1 + cfg.num_gnn_layers], cfg.hidden_dim, cfg.hidden_dim),
        "mlp": [],
    }
    dims = (cfg.hidden_dim + cfg.feat_dim,) + tuple(cfg.mlp_dims) + (1,)
    for i in range(len(dims) - 1):
        params["mlp"].append(
            {
                "w": _glorot(keys[2 + cfg.num_gnn_layers + i], (dims[i], dims[i + 1])),
                "b": jnp.zeros((dims[i + 1],)),
            }
        )
    if n_types:
        # independent key stream (fold_in, not a wider base split) so the
        # homogeneous leaves stay bit-identical to an untyped init
        emb_key, tower_rng = jax.random.split(jax.random.fold_in(rng, n_base))
        tower_keys = jax.random.split(tower_rng, n_types)
        params["typed"] = {
            # stage-1 additive input embedding per entity type
            "entity_type_emb": 0.02 * jax.random.normal(
                emb_key, (n_types, cfg.hidden_dim)),
            # stage-2 per-type weight blocks (type-partitioned residual
            # towers over the KV-fetched entity embeddings)
            "tower_w": jnp.stack([
                _glorot(tower_keys[t], (cfg.hidden_dim, cfg.hidden_dim))
                for t in range(n_types)
            ]),
            "tower_b": jnp.zeros((n_types, cfg.hidden_dim)),
        }
    return params


def _f32(fn):
    """Trace ``fn`` with every float32 matmul at full precision.  On the TPU
    an f32 matmul otherwise runs as a single bf16 pass, which would put the
    chip's scores and embeddings far outside float32 agreement with any
    reference; the CPU backend computes f32 products the same either way."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped


def _apply_towers(params, x, codes):
    """Per-type entity tower: rows whose type code is ``t`` are replaced by
    ``relu(x @ tower_w[t] + tower_b[t])``; rows with code ``-1`` (orders,
    shadows, untyped entities, padding) pass through unchanged.

    One static Python loop over T <= 7 types, each a masked select over a
    single dense matmul — the same formulation the batch, online, and fused
    Pallas paths all use, so the three stay numerically aligned.
    """
    tw, tb = params["typed"]["tower_w"], params["typed"]["tower_b"]
    out = x
    for t in range(tw.shape[0]):
        out = jnp.where((codes == t)[..., None],
                        jax.nn.relu(x @ tw[t] + tb[t]), out)
    return out


# ---------------------------------------------------------------------------
# Stage 1 — batch layer
# ---------------------------------------------------------------------------

@_f32
def lnn_stage1(params, cfg: LNNConfig, graph: PaddedGraph):
    """Input proj + first L-1 GNN layers.  Returns hidden states [N, H].

    The final-hop ``entity_{t-e} -> order_t`` edges are *masked out* here:
    per the paper they are consumed only by the last (speed-layer) GNN
    layer.  This is what makes the split exact — an order's stage-1 state
    depends only on its own raw features (see ``lnn_order_tower``), so the
    online path needs nothing but KV lookups of entity embeddings.
    """
    _, apply_fn = LAYER_REGISTRY[cfg.gnn_type]
    stage1_graph = graph._replace(
        nbr_mask=graph.nbr_mask * (graph.nbr_etype != EdgeType.ENTITY_TO_ORDER)
    )
    h = graph.features @ params["input"]["w"] + params["input"]["b"]
    h = h + params["type_emb"][graph.node_type]
    if "typed" in params and graph.tower is not None:
        # heterogeneous input: typed entity-snapshot vertices additionally
        # receive their per-entity-type embedding (tower < 0 rows add zero)
        emb = params["typed"]["entity_type_emb"]
        h = h + (graph.tower >= 0)[:, None] * emb[jnp.clip(graph.tower, 0)]
    h = jax.nn.relu(h)
    extra = {"relations": HGT_STAGE1_RELATIONS} if cfg.gnn_type == "hgt" else {}
    for layer in params["gnn"]:
        h = apply_fn(layer, h, stage1_graph, cfg.use_pallas, **extra)
    return h


@_f32
def lnn_order_tower(params, cfg: LNNConfig, order_feats):
    """Stage-1 state of an *order* node, computed locally from raw features.

    Because stage 1 masks final-hop edges, an order aggregates nothing in
    stage 1; each GNN layer reduces to its self-transform.  This is the
    cheap online recomputation the speed layer performs per checkout.
    """
    h = order_feats @ params["input"]["w"] + params["input"]["b"]
    h = h + params["type_emb"][NodeType.ORDER]
    h = jax.nn.relu(h)
    for layer in params["gnn"]:
        if cfg.gnn_type == "hgt":
            # HGT's update with an empty aggregate:
            # LN_order(alpha * b_A + (1 - alpha) * h)
            h = hgt_update(layer, jnp.zeros_like(h), h, NodeType.ORDER)
        else:
            # gcn, gat and sage share the self-transform form
            h = jax.nn.relu(h @ layer["w_self"] + layer["b"])
    return h


# ---------------------------------------------------------------------------
# Stage 2 — speed layer
# ---------------------------------------------------------------------------

def _last_layer_combine(params, cfg: LNNConfig, agg, self_h):
    """Final GNN layer math shared by the batch and online paths.

    ``agg`` is the (already weighted) neighbor aggregate in *input* space,
    ``self_h`` the node's own hidden state.
    """
    p = params["last"]
    if cfg.gnn_type == "hgt":
        # the target is an order: the order type's update, no ReLU
        return hgt_update(p, agg, self_h, NodeType.ORDER)
    if cfg.gnn_type == "gcn":
        # orders only receive ENTITY_TO_ORDER edges; use that etype's weight
        out = self_h @ p["w_self"] + agg @ p["w_nbr"][EdgeType.ENTITY_TO_ORDER]
    elif cfg.gnn_type == "sage":
        out = self_h @ p["w_self"] + agg @ p["w_nbr"]
    else:  # gat: agg is already in z-space (post-W); self term below
        out = agg + self_h @ p["w_self"]
    return jax.nn.relu(out + p["b"])


def _mlp(params, x):
    for i, layer in enumerate(params["mlp"]):
        x = x @ layer["w"] + layer["b"]
        if i + 1 < len(params["mlp"]):
            x = jax.nn.relu(x)
    return x[..., 0]


def _final_hop_aggregate(params, cfg: LNNConfig, h, graph: PaddedGraph):
    """Neighbor aggregate of the last layer, restricted to final-hop edges."""
    w_fin = graph.nbr_mask * (graph.nbr_etype == EdgeType.ENTITY_TO_ORDER)
    if cfg.gnn_type == "hgt":
        return hgt_attend(params["last"], h, graph._replace(nbr_mask=w_fin),
                          relations=(EdgeType.ENTITY_TO_ORDER,))
    if cfg.gnn_type == "gcn" or cfg.gnn_type == "sage":
        cnt = jnp.maximum(w_fin.sum(-1, keepdims=True), 1.0)
        return weighted_gather_sum(h, graph.nbr_idx, w_fin / cnt, cfg.use_pallas)
    # gat
    p = params["last"]
    z = h @ p["w"]
    s_dst = z @ p["a_dst"]
    logits = jnp.take(z @ p["a_src"], graph.nbr_idx, axis=0) + s_dst[:, None]
    logits = logits + p["a_et"][graph.nbr_etype]
    logits = jax.nn.leaky_relu(logits, 0.2)
    logits = jnp.where(w_fin > 0, logits, -1e9)
    attn = jax.nn.softmax(logits, axis=-1) * w_fin
    msgs = jnp.take(z, graph.nbr_idx, axis=0)
    return jnp.einsum("ndh,nd->nh", msgs, attn)


@_f32
def lnn_stage2_batch(params, cfg: LNNConfig, h, graph: PaddedGraph):
    """Speed-layer computation over the whole padded graph (training path).

    Returns logits [N]; only rows with node_type == ORDER are meaningful.
    """
    if "typed" in params and graph.tower is not None:
        # heterogeneous stage 2: per-type towers over entity rows before
        # the final-hop aggregation (order/shadow rows pass through)
        h = _apply_towers(params, h, graph.tower)
    agg = _final_hop_aggregate(params, cfg, h, graph)
    self_h = h
    g_out = _last_layer_combine(params, cfg, agg, self_h)
    x = jnp.concatenate([g_out, graph.features], axis=-1)
    return _mlp(params, x)


@_f32
def lnn_stage2_embed(params, cfg: LNNConfig, entity_emb, emb_mask, order_feats,
                     order_h=None, slot_type=None, slot_dt=None):
    """Online stage-2 *embedding*: everything up to (but excluding) the MLP
    head — the last GNN layer's output concatenated with the raw checkout
    features, ``[B, H + F]``.

    This is the representation the hybrid GNN→GBDT head
    (``repro.models.hybrid``) feeds to its booster; the pure-MLP scorer is
    exactly ``_mlp`` over the same tensor, so factoring it out changes no
    numerics.  ``slot_type``: optional [B, K] int type codes per entity
    slot (-1 = untyped/padding) — applies the per-type towers of a
    heterogeneous model before aggregation.  ``slot_dt``: [B, K] int time
    gaps per slot, the order's snapshot minus the served key's (HGT's
    relative temporal encoding; required for ``gnn_type == "hgt"``).
    """
    if order_h is None:
        order_h = lnn_order_tower(params, cfg, order_feats)
    if "typed" in params and slot_type is not None:
        entity_emb = _apply_towers(params, entity_emb, slot_type)
    if cfg.gnn_type in ("gcn", "sage"):
        cnt = jnp.maximum(emb_mask.sum(-1, keepdims=True), 1.0)
        agg = jnp.einsum("bkh,bk->bh", entity_emb, emb_mask / cnt)
    elif cfg.gnn_type == "hgt":
        agg = hgt_slot_attend(params["last"], order_h, entity_emb, emb_mask,
                              slot_dt)
    else:  # gat
        p = params["last"]
        z = entity_emb @ p["w"]
        logits = z @ p["a_src"] + ((order_h @ p["w"]) @ p["a_dst"])[:, None]
        logits = logits + p["a_et"][EdgeType.ENTITY_TO_ORDER]
        logits = jax.nn.leaky_relu(logits, 0.2)
        logits = jnp.where(emb_mask > 0, logits, -1e9)
        attn = jax.nn.softmax(logits, axis=-1) * emb_mask
        agg = jnp.einsum("bkh,bk->bh", z, attn)
    g_out = _last_layer_combine(params, cfg, agg, order_h)
    return jnp.concatenate([g_out, order_feats], axis=-1)


@_f32
def lnn_stage2_online(params, cfg: LNNConfig, entity_emb, emb_mask, order_feats,
                      order_h=None, slot_type=None, slot_dt=None):
    """Online scoring path: KV-fetched entity embeddings -> risk logit.

    entity_emb: [B, K, H] stage-1 embeddings of the ≤K linked effective
    entities (zero rows where absent); emb_mask: [B, K]; order_feats: [B, F]
    raw checkout features; order_h: [B, H] the order's own stage-1 hidden
    state — optional, recomputed from ``order_feats`` when omitted (always
    valid: stage 1 masks final-hop edges, so an order's stage-1 state is a
    pure function of its own raw features, see ``lnn_order_tower``).
    ``slot_type``: optional [B, K] int entity-type codes (heterogeneous
    models; -1 = padding/untyped slot).  ``slot_dt``: [B, K] int time gap
    per slot, the order's snapshot minus the served key's (``hgt`` only).

    With ``cfg.use_pallas`` the whole path — tower, masked aggregation,
    last-layer combine, MLP logit — runs as ONE fused Pallas launch
    (``kernels.stage2_score``; interpret mode on CPU).  The tower is then
    always recomputed inside the kernel, so a supplied ``order_h`` is
    ignored on that path.
    """
    if cfg.use_pallas:
        from repro.kernels.ops import stage2_score

        return stage2_score(params, cfg.gnn_type, entity_emb, emb_mask,
                            order_feats, slot_type=slot_type, slot_dt=slot_dt)
    x = lnn_stage2_embed(params, cfg, entity_emb, emb_mask, order_feats,
                         order_h=order_h, slot_type=slot_type, slot_dt=slot_dt)
    return _mlp(params, x)


# ---------------------------------------------------------------------------
# End-to-end
# ---------------------------------------------------------------------------

def lnn_forward(params, cfg: LNNConfig, graph: PaddedGraph):
    """Full forward (training): stage2 ∘ stage1.  Logits [N]."""
    h = lnn_stage1(params, cfg, graph)
    return lnn_stage2_batch(params, cfg, h, graph)


def lnn_loss(params, cfg: LNNConfig, graph: PaddedGraph):
    """Masked weighted BCE over effective orders."""
    logits = lnn_forward(params, cfg, graph)
    is_order = (graph.node_type == NodeType.ORDER).astype(jnp.float32)
    mask = graph.label_mask * is_order
    y = graph.label
    logp = jax.nn.log_sigmoid(logits)
    lognp = jax.nn.log_sigmoid(-logits)
    per = -(cfg.pos_weight * y * logp + (1.0 - y) * lognp)
    return (per * mask).sum() / jnp.maximum(mask.sum(), 1.0)
