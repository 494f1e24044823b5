"""Operations and bytes HGT's algorithm needs in the LNN, from shapes alone.

The counts ``work_counts.py`` keeps for gcn, gat and sage, here for
``gnn_type="hgt"`` (HGT, arXiv 2003.01332): what the rooflines and the MFU
of an HGT cell divide by time.  They count the algorithm, never an
implementation: each node's typed linears are counted for its own type
only, the per-head relation transforms as ``heads`` blocks of
``d_k x d_k``, and an edge's logit and message as ``2 * H`` multiply-adds
however a kernel gathers them.  A multiply-add is two operations.  Bytes
are each input and weight read once and each output written once, in
float32.

Per stage-1 layer over ``N`` real nodes and ``E`` real edges, with ``R = 3``
stage-1 relations and ``T = max_history + 1`` gaps in the relative
temporal encoding's table:

    typed linears        4 N H^2       K, Q, M, A
    relation transforms  R N 2 H d_k   W^ATT and W^MSG per (relation, head)
    edges                2 E H         logit and weighted message
    RTE                  T H^2 + 2 E H T-Linear over the table of gaps
                                       0..max_history; the gap's rows added
                                       to each edge's key and message
"""
from __future__ import annotations

F32 = 4
#: relations stage 1 aggregates over (shadow->entity, entity->shadow,
#: entity history)
STAGE1_RELATIONS = 3
#: node types with their own linears (order, shadow, entity)
NODE_TYPES = 3


def _dims(model: dict):
    H, F, L = model["hidden_dim"], model["feat_dim"], model["num_gnn_layers"]
    nh = model["num_heads"]
    return H, F, L, nh, H // nh


def layer_params(model: dict) -> int:
    """Weights of one HGT layer: four typed linears, W^ATT and W^MSG per
    relation and head, mu, the RTE linear, gates and LayerNorms."""
    H, _, _, nh, dk = _dims(model)
    R = 4
    return (4 * NODE_TYPES * (H * H + H) + 2 * R * nh * dk * dk + R * nh
            + H * H + H + NODE_TYPES + 2 * NODE_TYPES * H)


def stage1_graph(model: dict, nodes: int, edges: int) -> tuple[float, float]:
    """(operations, bytes) of stage 1 (input projection and L-1 HGT
    layers) over ``nodes`` real nodes and ``edges`` real stage-1 edges."""
    H, F, L, _, dk = _dims(model)
    T = model.get("max_history", 8) + 1
    per_layer = (4 * nodes * H * H + STAGE1_RELATIONS * nodes * 2 * H * dk
                 + 2 * edges * H + T * H * H + 2 * edges * H)
    macs = nodes * F * H + (L - 1) * per_layer
    params = F * H + 5 * H + (L - 1) * layer_params(model)
    byts = nodes * F * F32 + (L - 1) * (2 * nodes * H * F32
                                        + edges * 3 * F32)
    return 2.0 * macs, float(byts + params * F32)


def stage2_params(model: dict) -> int:
    """Weights stage 2 must read: the input projection and the order type's
    embedding, per stage-1 layer the order type's A bias, gate and
    LayerNorm (an order has no stage-1 in-edge), the last layer's RTE
    linear, the entity type's K and M linears with W^ATT / W^MSG of the
    final hop folded in (constant per model), the order type's Q, A, gate
    and LayerNorm, mu of the final hop, and the MLP head.  Other types'
    and relations' weights are not read."""
    H, F, L, nh, _ = _dims(model)
    dims = (H + F,) + tuple(model["mlp_dims"]) + (1,)
    n = F * H + 2 * H + (L - 1) * (3 * H + 1)
    n += 5 * (H * H + H) + nh + 1 + 2 * H
    n += sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
    return n


def stage2_order_macs(model: dict, k_slots: float) -> float:
    """Multiply-adds of one order through online stage 2: the order tower
    (an order has no stage-1 in-edge, so each layer is a gate and a
    LayerNorm), the RTE rows of its slots, keys and messages of the entity
    type with W^ATT / W^MSG folded into them (constant per model), its
    query, the slots' logits and weighted messages, the A linear, the MLP."""
    H, F, L, _, _ = _dims(model)
    dims = (H + F,) + tuple(model["mlp_dims"]) + (1,)
    macs = F * H
    macs += k_slots * 3 * H * H            # RTE linear, folded K and M
    macs += H * H                          # Q
    macs += k_slots * 2 * H                # logits, weighted sum
    macs += H * H                          # A
    macs += sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    return macs


def stage2_flush(model: dict, orders: int, k_slots: float) -> tuple[float, float]:
    """(operations, bytes) of one stage-2 launch over ``orders`` real
    orders with ``k_slots`` KV slots each."""
    H, F, _, _, _ = _dims(model)
    ops = 2.0 * orders * stage2_order_macs(model, k_slots)
    per_order = (k_slots * H + 2 * k_slots + F + 1) * F32
    return ops, float(orders * per_order + stage2_params(model) * F32)
