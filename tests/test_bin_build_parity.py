"""Parity of the array-built refresh bins with the per-edge loop builders.

``pad_graph`` and ``IncrementalDDSBuilder.build_subgraph`` build each
refresh bin with array operations.  The loop versions they replaced live on
here, verbatim, as the reference: every field of every output must be equal
(``np.array_equal``), including the degree-cap branch that no benchmark
cell reaches and the edgeless graph the benchmark pads to warm stage 1.
"""
from bisect import bisect_left

import numpy as np
import pytest

from repro.core.dds import DDSGraph, IncrementalDDSBuilder, check_no_future_leak
from repro.core.graph import COOGraph, EdgeType, NodeType, PaddedGraph, pad_graph
from repro.core.hetero import tag_entity, type_codes_array
from repro.core.partition import IncrementalPartitioner
from repro.data import SynthConfig, generate_event_stream


# ------------------------------------------------------- loop references
def loop_pad_graph(g, num_nodes=None, max_deg=None, deg_cap_policy="recent"):
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

    # sort edges by dst for grouped fill
    order = np.argsort(g.dst, kind="stable")
    src_s, dst_s, et_s = g.src[order], g.dst[order], g.etype[order]
    starts = np.searchsorted(dst_s, np.arange(num_nodes), side="left")
    ends = np.searchsorted(dst_s, np.arange(num_nodes), side="right")
    snap = g.snapshot
    for v in np.nonzero(ends > starts)[0]:
        s, e = starts[v], ends[v]
        srcs = src_s[s:e]
        ets = et_s[s:e]
        if e - s > max_deg:
            if deg_cap_policy == "recent":
                keep = np.argsort(-snap[srcs], kind="stable")[:max_deg]
            else:
                keep = np.arange(max_deg)
            srcs, ets = srcs[keep], ets[keep]
        k = srcs.size
        nbr_idx[v, :k] = srcs
        nbr_mask[v, :k] = 1.0
        nbr_etype[v, :k] = ets

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
        features=feat, nbr_idx=nbr_idx, nbr_mask=nbr_mask, nbr_etype=nbr_etype,
        node_type=ntype, snapshot=snapshot, label=label, label_mask=label_mask,
        tower=tower,
    )


def loop_tower_codes(n_nodes: int, entity_snap_ids: dict) -> np.ndarray | None:
    if not entity_snap_ids:
        return None
    ents = np.fromiter((pair[0] for pair in entity_snap_ids),
                       np.int64, len(entity_snap_ids))
    codes = type_codes_array(ents)
    if not (codes >= 0).any():
        return None
    tower = np.full(n_nodes, -1, np.int32)
    nids = np.fromiter(entity_snap_ids.values(), np.int64, len(entity_snap_ids))
    tower[nids] = codes
    return tower


def loop_build_subgraph(self, entities) -> DDSGraph:
    ents = {int(e) for e in entities}
    touched = sorted({o for e in ents
                      for o in self._entity_orders.get(e, ())})
    for o in touched:
        for e2 in self._order_entities[o]:
            if e2 not in ents:
                raise ValueError(
                    f"entity set is not component-closed: order {o} links "
                    f"entity {e2} outside the set"
                )
    n_sub = len(touched)
    order_local = {o: i for i, o in enumerate(touched)}
    pairs = sorted((e, t) for e in ents for t in self._active.get(e, ()))
    entity_snap_ids = {p: 2 * n_sub + i for i, p in enumerate(pairs)}

    src, dst, et = [], [], []
    for o in touched:
        t = self._order_snapshot[o]
        s_node = n_sub + order_local[o]
        for ent in self._order_entities[o]:
            e_node = entity_snap_ids[(ent, t)]
            src.append(s_node); dst.append(e_node); et.append(EdgeType.SHADOW_TO_ENTITY)
            src.append(e_node); dst.append(s_node); et.append(EdgeType.ENTITY_TO_SHADOW)
    for ent in sorted(ents):
        snaps = self._active.get(ent, [])
        for j, t in enumerate(snaps):
            cur = entity_snap_ids[(ent, t)]
            src.append(cur); dst.append(cur); et.append(EdgeType.ENTITY_HIST)
            if self.entity_history == "consecutive":
                past = snaps[j - 1 : j] if j > 0 else []
            else:
                past = snaps[:j]
                if self.max_history is not None:
                    past = past[-self.max_history:]
            for tp in past:
                src.append(entity_snap_ids[(ent, tp)]); dst.append(cur)
                et.append(EdgeType.ENTITY_HIST)
    last_hop: dict = {}
    for o in touched:
        t = self._order_snapshot[o]
        lo = order_local[o]
        for ent in self._order_entities[o]:
            snaps = self._active[ent]
            idx = bisect_left(snaps, t) - 1
            if idx < 0:
                continue
            t_e = snaps[idx]
            e_node = entity_snap_ids[(ent, t_e)]
            src.append(e_node); dst.append(lo); et.append(EdgeType.ENTITY_TO_ORDER)
            last_hop.setdefault(lo, []).append((ent, t_e, e_node))

    n_nodes = 2 * n_sub + len(entity_snap_ids)
    features = np.zeros((n_nodes, self.feat_dim), np.float32)
    node_type = np.full(n_nodes, NodeType.ENTITY, np.int32)
    node_type[:n_sub] = NodeType.ORDER
    node_type[n_sub : 2 * n_sub] = NodeType.SHADOW
    snapshot = np.zeros(n_nodes, np.int32)
    label = np.zeros(n_nodes, np.float32)
    label_mask = np.zeros(n_nodes, np.float32)
    label_mask[:n_sub] = 1.0
    for o in touched:
        lo = order_local[o]
        features[lo] = self._order_features[o]
        features[n_sub + lo] = self._order_features[o]
        snapshot[lo] = snapshot[n_sub + lo] = self._order_snapshot[o]
        label[lo] = self._labels[o]
    for (ent, t), nid in entity_snap_ids.items():
        snapshot[nid] = t
    coo = COOGraph(
        num_nodes=n_nodes,
        src=np.asarray(src, np.int64),
        dst=np.asarray(dst, np.int64),
        etype=np.asarray(et, np.int32),
        features=features, node_type=node_type, snapshot=snapshot,
        label=label, label_mask=label_mask,
        tower=loop_tower_codes(n_nodes, entity_snap_ids),
    )
    return DDSGraph(coo=coo, num_orders=n_sub,
                    entity_snap_ids=entity_snap_ids, last_hop=last_hop)


def assert_padded_equal(a: PaddedGraph, b: PaddedGraph):
    for name in PaddedGraph._fields:
        x, y = getattr(a, name), getattr(b, name)
        if x is None or y is None:
            assert x is None and y is None, name
            continue
        assert x.dtype == y.dtype and np.array_equal(x, y), name


# ------------------------------------------------------------- pad_graph
def _random_coo(rng, tower: bool) -> COOGraph:
    """A graph whose in-degrees pass every cap under test (a hub of 60
    in-edges) and whose source snapshots tie often (four values)."""
    n = 40
    hub = np.full(60, 3)
    dst = np.concatenate([hub, rng.integers(0, n, 300),
                          rng.integers(0, 4, 80)])
    src = rng.integers(0, n, dst.size)
    return COOGraph(
        num_nodes=n, src=src.astype(np.int64), dst=dst.astype(np.int64),
        etype=rng.integers(0, EdgeType.NUM, dst.size).astype(np.int32),
        features=rng.standard_normal((n, 5)).astype(np.float32),
        node_type=rng.integers(0, 3, n).astype(np.int32),
        snapshot=rng.integers(0, 4, n).astype(np.int32),
        label=rng.integers(0, 2, n).astype(np.float32),
        label_mask=rng.integers(0, 2, n).astype(np.float32),
        tower=rng.integers(-1, 4, n).astype(np.int32) if tower else None,
    )


def _edgeless_coo(tower: bool) -> COOGraph:
    """The one-node graph with no edges the benchmark pads to warm every
    stage-1 bin shape."""
    return COOGraph(num_nodes=1, src=np.zeros(0, np.int64),
                    dst=np.zeros(0, np.int64), etype=np.zeros(0, np.int32),
                    features=np.zeros((1, 48), np.float32),
                    node_type=np.zeros(1, np.int32),
                    snapshot=np.zeros(1, np.int32),
                    label=np.zeros(1, np.float32),
                    label_mask=np.zeros(1, np.float32),
                    tower=np.full(1, -1, np.int32) if tower else None)


def _dds_coo(seed: int, tower: bool) -> COOGraph:
    """A DDS graph from a seeded stream: the bins' own edge structure."""
    events, g, _ = generate_event_stream(
        SynthConfig(num_users=30, num_rings=2, seed=seed), rate_per_s=500.0)
    b = IncrementalDDSBuilder(g.order_features.shape[1], "all", None)
    for ev in events:
        ents = [tag_entity(e, e % 4) for e in ev.entities] if tower \
            else ev.entities
        b.add_order(ents, ev.snapshot, ev.features, ev.label)
    return b.build().coo


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


@pytest.mark.parametrize("policy", ["recent", "first"])
@pytest.mark.parametrize("max_deg", [1, 4, 32, None])
@pytest.mark.parametrize("kind,seed", [("random", 0), ("random", 1),
                                       ("random", 2), ("dds", 3),
                                       ("edgeless", 0)])
def test_pad_graph_matches_loop_reference(kind, seed, max_deg, policy):
    for tower in (False, True):
        if kind == "random":
            g = _random_coo(np.random.default_rng(seed), tower)
        elif kind == "dds":
            g = _dds_coo(seed, tower)
        else:
            g = _edgeless_coo(tower)
        if kind == "random":
            assert g.in_degrees().max() > 32       # every cap truncates
        for n in (g.num_nodes, _pow2(g.num_nodes) * 2):
            assert_padded_equal(
                pad_graph(g, num_nodes=n, max_deg=max_deg,
                          deg_cap_policy=policy),
                loop_pad_graph(g, num_nodes=n, max_deg=max_deg,
                               deg_cap_policy=policy))


# --------------------------------------------------------- build_subgraph
@pytest.fixture(scope="module")
def events():
    evs, _, _ = generate_event_stream(
        SynthConfig(num_users=70, num_rings=3, feature_noise=0.8, seed=11),
        rate_per_s=500.0)
    return evs


@pytest.mark.parametrize("tagged", [False, True])
@pytest.mark.parametrize("history,max_history",
                         [("all", None), ("all", 4), ("consecutive", None),
                          ("all", 0)])
def test_build_subgraph_matches_loop_reference(events, history, max_history,
                                               tagged):
    b = IncrementalDDSBuilder(events[0].features.shape[0], history,
                              max_history)
    part = IncrementalPartitioner()
    for ev in events:
        ents = [tag_entity(e, e % 4) for e in ev.entities] if tagged \
            else [int(e) for e in ev.entities]
        b.add_order(ents, ev.snapshot, ev.features, ev.label)
        part.add_order(ents)
    communities = sorted({part.community_of(e) for e in part.assignment()})
    largest = max(communities, key=lambda c: len(part.members(c)))
    picks = [[communities[0]], [communities[-1]], [largest],
             communities[1:4], communities]

    def members(pick):
        return {e for c in pick for e in part.members(c)}

    sets = [members(p) for p in picks] + [set(), {max(part.assignment()) + 7}]
    for ents in sets:
        new, ref = b.build_subgraph(ents), loop_build_subgraph(b, ents)
        check_no_future_leak(new)
        assert new.num_orders == ref.num_orders
        assert new.entity_snap_ids == ref.entity_snap_ids
        assert new.last_hop == ref.last_hop
        if tagged and new.entity_snap_ids:
            assert new.coo.tower is not None
        for max_deg, policy in ((None, "recent"), (2, "recent"), (2, "first")):
            assert_padded_equal(
                pad_graph(new.coo, max_deg=max_deg, deg_cap_policy=policy),
                pad_graph(ref.coo, max_deg=max_deg, deg_cap_policy=policy))

    # a set that is not component-closed raises the same error
    multi = next(ev for ev in events if len(ev.entities) >= 2)
    first = multi.entities[0]
    ents = members([part.community_of(tag_entity(first, first % 4)
                                      if tagged else first)])
    withheld = multi.entities[1]
    ents.discard(tag_entity(withheld, withheld % 4) if tagged else withheld)
    with pytest.raises(ValueError) as new_err:
        b.build_subgraph(ents)
    with pytest.raises(ValueError) as ref_err:
        loop_build_subgraph(b, ents)
    assert "component-closed" in str(new_err.value)
    assert str(new_err.value) == str(ref_err.value)
