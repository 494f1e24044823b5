"""Operations and bytes the LNN's algorithm needs, from shapes alone.

These are the counts the rooflines and the MFU figures divide by time.
They count the algorithm, never an implementation: a gather over E edges
costs E * H multiply-adds however a kernel performs it (the stage-1
kernels gather by a one-hot matmul of O(N^2 * D) work, which is not
counted).  A multiply-add is two operations.  Bytes are the least a kernel
must move between HBM and the core: each input and weight read once, each
output written once, in float32.
"""
from __future__ import annotations

F32 = 4
#: edge types stage 1 aggregates over (shadow->entity, entity->shadow,
#: entity history); the final-hop type is the speed layer's alone
STAGE1_ETYPES = 3


def stage2_params(model: dict) -> int:
    """Weights stage 2 reads: order tower, last GNN layer, MLP head."""
    H, F, L = model["hidden_dim"], model["feat_dim"], model["num_gnn_layers"]
    dims = (H + F,) + tuple(model["mlp_dims"]) + (1,)
    n = F * H + 2 * H + (L - 1) * (H * H + H)
    n += {"gcn": 2 * H * H + H, "sage": 2 * H * H + H,
          "gat": 2 * H * H + 3 * H + 1}[model["gnn_type"]]
    n += sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
    return n


def stage2_order_macs(model: dict, k_slots: int) -> int:
    """Multiply-adds of one order through online stage 2."""
    H, F, L = model["hidden_dim"], model["feat_dim"], model["num_gnn_layers"]
    dims = (H + F,) + tuple(model["mlp_dims"]) + (1,)
    macs = F * H + (L - 1) * H * H                      # order tower
    if model["gnn_type"] == "gat":
        macs += k_slots * H * H + k_slots * H           # z = emb W, s_src
        macs += H * H + H                               # s_dst
        macs += k_slots * H + H * H                     # attention sum, self
    else:
        macs += k_slots * H + 2 * H * H                 # mean, combine
    macs += sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    return macs


def stage2_flush(model: dict, orders: int, k_slots: int) -> tuple[float, float]:
    """(operations, bytes) of one stage-2 launch over ``orders`` real
    orders with ``k_slots`` KV slots each."""
    H, F = model["hidden_dim"], model["feat_dim"]
    ops = 2.0 * orders * stage2_order_macs(model, k_slots)
    per_order = (k_slots * H + k_slots + F + 1) * F32
    return ops, float(orders * per_order + stage2_params(model) * F32)


def stage1_graph(model: dict, nodes: int, edges: int) -> tuple[float, float]:
    """(operations, bytes) of stage 1 (input projection and L-1 GNN
    layers) over a graph of ``nodes`` real nodes and ``edges`` real
    stage-1 edges."""
    H, F, L = model["hidden_dim"], model["feat_dim"], model["num_gnn_layers"]
    gnn = model["gnn_type"]
    macs = nodes * F * H
    per_layer = {"gcn": (1 + STAGE1_ETYPES) * nodes * H * H + edges * H,
                 "sage": 2 * nodes * H * H + edges * H,
                 "gat": 2 * nodes * H * H + 2 * nodes * H + edges * H}[gnn]
    macs += (L - 1) * per_layer
    params = F * H + 5 * H + (L - 1) * {"gcn": (1 + 4) * H * H + H,
                                        "sage": 2 * H * H + H,
                                        "gat": 2 * H * H + 3 * H + 4}[gnn]
    byts = nodes * F * F32 + (L - 1) * (2 * nodes * H * F32 + edges * 2 * F32)
    return 2.0 * macs, float(byts + params * F32)


def roofline_s(ops: float, byts: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(ops / peak["bf16_flops_per_s"], byts / peak["hbm_bytes_per_s"])
