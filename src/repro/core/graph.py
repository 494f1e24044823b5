"""Device-friendly graph containers.

TPU/XLA require static shapes, so graphs are stored as *padded in-neighbor
lists* rather than dynamic CSR: for every node a fixed-width row of neighbor
indices plus a mask.  This is the layout consumed by the GNN layers and by
the ``csr_spmm`` / ``edge_softmax`` Pallas kernels.

Node/edge-type vocabularies for the DDS graph live here so every module
agrees on the integer codes.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import numpy as np

# ---------------------------------------------------------------------------
# DDS vocabularies (paper Table 2)
# ---------------------------------------------------------------------------

class NodeType:
    ORDER = 0        # effective order_t (carries the label)
    SHADOW = 1       # shadow clone order_t^s (no label, feeds entities)
    ENTITY = 2       # entity_t snapshot vertex
    PAD = 3


class EdgeType:
    SHADOW_TO_ENTITY = 0   # order_t^s -> entity_t   (same snapshot)
    ENTITY_TO_SHADOW = 1   # entity_t -> order_t^s   (same snapshot)
    ENTITY_HIST = 2        # entity_{t-i} -> entity_t (incl. self loop i=0)
    ENTITY_TO_ORDER = 3    # entity_{t-e} -> order_t (the final 1-hop edges)
    NUM = 4


# ---------------------------------------------------------------------------
# Padded graph (pytree) consumed by GNN layers
# ---------------------------------------------------------------------------

class PaddedGraph(NamedTuple):
    """Fixed-shape graph for one community (or a batch of merged communities).

    All arrays are padded to ``num_nodes`` rows and ``max_deg`` neighbor
    columns.  ``nbr_idx`` points at *source* nodes of incoming edges; padded
    slots point at row 0 with ``nbr_mask == 0``.
    """

    features: jax.Array      # [N, F] float — raw features (zeros for entities)
    nbr_idx: jax.Array       # [N, D] int32 — in-neighbor node index
    nbr_mask: jax.Array      # [N, D] float32 — 1 for real edges
    nbr_etype: jax.Array     # [N, D] int32 — EdgeType codes (0 where padded)
    node_type: jax.Array     # [N] int32 — NodeType codes (PAD for padding)
    snapshot: jax.Array      # [N] int32 — snapshot index t (-1 for padding)
    label: jax.Array         # [N] float32 — fraud label (orders only)
    label_mask: jax.Array    # [N] float32 — 1 where label is valid
    # [N] int32 entity-type tower codes (-1 = untyped/non-entity), or None
    # on a homogeneous graph — the trailing default keeps untyped pytrees
    # (and their jit caches) byte-identical to the pre-hetero layout
    tower: jax.Array | None = None

    @property
    def num_nodes(self) -> int:
        return self.features.shape[0]

    @property
    def max_deg(self) -> int:
        return self.nbr_idx.shape[1]


@dataclass
class COOGraph:
    """Host-side (numpy) directed graph in COO form, before padding."""

    num_nodes: int
    src: np.ndarray          # [E] int64
    dst: np.ndarray          # [E] int64
    etype: np.ndarray        # [E] int32
    features: np.ndarray     # [N, F]
    node_type: np.ndarray    # [N]
    snapshot: np.ndarray     # [N]
    label: np.ndarray        # [N]
    label_mask: np.ndarray   # [N]
    # [N] entity-type tower codes (-1 = untyped/non-entity); None on
    # homogeneous graphs (see repro.core.hetero)
    tower: np.ndarray | None = None

    def in_degrees(self) -> np.ndarray:
        deg = np.zeros(self.num_nodes, np.int64)
        np.add.at(deg, self.dst, 1)
        return deg


def pad_graph(
    g: COOGraph,
    num_nodes: int | None = None,
    max_deg: int | None = None,
    deg_cap_policy: str = "recent",
) -> PaddedGraph:
    """Convert a COOGraph to a PaddedGraph.

    If a node's in-degree exceeds ``max_deg`` the excess edges are dropped:
    ``deg_cap_policy='recent'`` keeps edges whose *source snapshot* is most
    recent (matches the DDS intuition that fresh history matters most);
    ``'first'`` keeps arbitrary first-encountered edges.
    """
    n_real = g.num_nodes
    if num_nodes is None:
        num_nodes = n_real
    if num_nodes < n_real:
        raise ValueError(f"num_nodes {num_nodes} < real {n_real}")
    deg = g.in_degrees()
    if max_deg is None:
        max_deg = int(deg.max()) if deg.size else 1
    max_deg = max(int(max_deg), 1)

    nbr_idx = np.zeros((num_nodes, max_deg), np.int32)
    nbr_mask = np.zeros((num_nodes, max_deg), np.float32)
    nbr_etype = np.zeros((num_nodes, max_deg), np.int32)

    # Group edges by destination, keeping each destination's edge order
    # (the contract with the DDS builders).  Only a destination over the cap
    # under 'recent' reorders its edges: most recent source snapshot first,
    # ties in edge order.
    over = deg[g.dst] > max_deg
    if deg_cap_policy == "recent" and over.any():
        key = np.where(over, -np.asarray(g.snapshot, np.int64)[g.src], 0)
        order = np.lexsort((key, g.dst))
    else:
        order = np.argsort(g.dst, kind="stable")
    dst_s = g.dst[order]
    # rank of each edge within its destination; the first max_deg are kept
    rank = np.arange(dst_s.size) - (np.cumsum(deg) - deg)[dst_s]
    keep = rank < max_deg
    rows, cols = dst_s[keep], rank[keep]
    nbr_idx[rows, cols] = g.src[order][keep]
    nbr_mask[rows, cols] = 1.0
    nbr_etype[rows, cols] = g.etype[order][keep]

    feat = np.zeros((num_nodes, g.features.shape[1]), np.float32)
    feat[:n_real] = g.features
    ntype = np.full(num_nodes, NodeType.PAD, np.int32)
    ntype[:n_real] = g.node_type
    snapshot = np.full(num_nodes, -1, np.int32)
    snapshot[:n_real] = g.snapshot
    label = np.zeros(num_nodes, np.float32)
    label[:n_real] = g.label
    label_mask = np.zeros(num_nodes, np.float32)
    label_mask[:n_real] = g.label_mask
    tower = None
    if g.tower is not None:
        tower = np.full(num_nodes, -1, np.int32)
        tower[:n_real] = g.tower

    return PaddedGraph(
        features=feat,
        nbr_idx=nbr_idx,
        nbr_mask=nbr_mask,
        nbr_etype=nbr_etype,
        node_type=ntype,
        snapshot=snapshot,
        label=label,
        label_mask=label_mask,
        tower=tower,
    )
