"""Pallas TPU kernel: fused online stage-2 scoring (the speed-layer hot path).

``lnn_stage2_online`` is the computation every streamed checkout request
crosses after its KV lookups.  The unfused path issues four separate
dispatches per micro-batch — order tower, masked aggregation, last-layer
combine, MLP head — each reading/writing HBM.  This kernel performs the
whole thing in ONE launch over a padded micro-batch:

    tower    h = relu(feats @ W_in + b_in + type_emb[ORDER])
                 then (L-1) x  relu(h @ W_self_l + b_l)        (stage-1 self
                                                                transforms)
    agg      a = masked mean (gcn/sage) or masked attention (gat)
                 over the KV-fetched entity embeddings          (final hop)
    combine  g = relu(h @ W_self + a @ W_nbr + b)               (last GNN layer)
    logit    y = MLP([g ; feats])                               (risk head)

The ``[g ; feats]`` concatenation is folded into the MLP's first layer by
splitting its weight row-wise (``w0[:H]`` / ``w0[H:]``), so no concat ever
materialises.  Layer counts are static per config, so the tower and MLP
loops unroll at trace time, and so does the entity-slot aggregation over the
fixed width K (Mosaic cannot index a loaded value inside a ``fori_loop``).
Every matmul is traced at highest precision: the chip's default would run
an f32 product as one bf16 pass.

Block sizing follows ``stream.microbatch.bucket_size``: the batch dimension
tiles in power-of-two blocks (capped at ``block_b``), so every micro-batch
bucket the scheduler can emit (1, 2, 4, ..., max_batch) maps to one grid
step with zero re-padding.  Weights are tiny (H <= 256) and ride along
whole in VMEM.

VMEM budget per program (defaults bb=128, K=8, H=64, F=16, f32):
    emb tile   bb x K x H = 128*8*64*4 = 256 KiB
    weights    ~(F*H + L*H^2 + (H+F)*m0 + ...) * 4 ~= 100 KiB
    activations bb x H few copies      ~= 100 KiB          << 16 MiB VMEM

Like the other kernels in this package the same ``pallas_call`` runs in
interpret mode on CPU (the tier-1 correctness oracle) and compiles natively
on TPU (``tests/test_tpu_compile.py`` compiles it for a v5e).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils.padding import ceil_div


def _bucket_block(b: int, cap: int) -> int:
    """Next power-of-two >= b, capped — mirrors ``stream.microbatch.bucket_size``."""
    p = 1
    while p < b and p < cap:
        p *= 2
    return min(p, cap)


def _make_stage2_kernel(gnn_type: str, n_tower: int, n_mlp_extra: int,
                        typed: bool = False, n_types: int = 0):
    """Build the kernel body for a static (gnn_type, depth) configuration.

    ``typed``: heterogeneous variant — a fourth data input carries per-slot
    entity-type codes and two extra weight refs carry the type-partitioned
    tower blocks ``[T, H, H]`` / ``[T, H]``, applied to each entity slot
    before aggregation (code -1 = padding/untyped slot, passthrough).  The
    untyped kernel signature and body are byte-identical to the pre-hetero
    version — bit-parity gates on homogeneous configs see the same launch.
    """

    def kernel(*refs):
        # f32 matmuls at full precision: the chip's default runs them as
        # one bf16 pass (the CPU interpreter is unaffected)
        with jax.default_matmul_precision("highest"):
            body(*refs)

    def body(*refs):
        if typed:
            emb_ref, mask_ref, feats_ref, st_ref = refs[0:4]
            woff = 4
        else:
            emb_ref, mask_ref, feats_ref = refs[0:3]
            woff = 3
        w_in_ref, b_in_ref, type_ref, tw_ref, tb_ref = refs[woff:woff + 5]
        if typed:
            ttw_ref, ttb_ref = refs[woff + 5:woff + 7]
            rest = refs[woff + 7:]
        else:
            rest = refs[woff + 5:]
        if gnn_type == "gat":
            (w_self_ref, b_last_ref, w_gat_ref,
             a_src_ref, a_dst_ref, a_et_ref) = rest[0:6]
            mlp_refs = rest[6:-1]
        else:
            w_self_ref, w_nbr_ref, b_last_ref = rest[0:3]
            mlp_refs = rest[3:-1]
        out_ref = refs[-1]

        emb = emb_ref[...].astype(jnp.float32)      # [bb, K, H]
        mask = mask_ref[...].astype(jnp.float32)    # [bb, K]
        feats = feats_ref[...].astype(jnp.float32)  # [bb, F]
        bb, K, H = emb.shape

        # ---- per-type entity towers (heterogeneous models only) ----
        if typed:
            st = st_ref[...]                        # [bb, K, 1] int32 codes
            ttw = ttw_ref[...]                      # [T, H, H]
            ttb = ttb_ref[...]                      # [T, H]
            emb0 = emb
            for t in range(n_types):
                tr = jnp.maximum(
                    emb0.reshape(bb * K, H) @ ttw[t] + ttb[t], 0.0
                ).reshape(bb, K, H)
                emb = jnp.where(st == t, tr, emb)

        # ---- order tower: input projection + stage-1 self transforms ----
        h = feats @ w_in_ref[...] + b_in_ref[...] + type_ref[...]
        h = jnp.maximum(h, 0.0)
        for li in range(n_tower):
            h = jnp.maximum(h @ tw_ref[li] + tb_ref[li], 0.0)

        # ---- masked aggregation over the K entity slots ----
        if gnn_type in ("gcn", "sage"):
            cnt = jnp.maximum(mask.sum(-1, keepdims=True), 1.0)
            wght = mask / cnt                        # [bb, K]

            agg = jnp.zeros((bb, H), jnp.float32)
            for k in range(K):                       # static: K is fixed
                agg = agg + emb[:, k, :] * wght[:, k:k + 1]
            g = h @ w_self_ref[...] + agg @ w_nbr_ref[...]
        else:  # gat: attention over the slots in z-space
            w = w_gat_ref[...]
            z = (emb.reshape(bb * K, H) @ w).reshape(bb, K, H)
            s_dst = (h @ w) @ a_dst_ref[...]                          # [bb, 1]
            s_src = (z.reshape(bb * K, H) @ a_src_ref[...]).reshape(bb, K)
            logits = s_src + s_dst + a_et_ref[0, 0]
            logits = jnp.where(logits >= 0, logits, 0.2 * logits)     # leaky relu
            logits = jnp.where(mask > 0, logits, -1e9)
            m = jnp.max(logits, axis=-1, keepdims=True)
            e = jnp.exp(logits - m)
            attn = (e / jnp.sum(e, axis=-1, keepdims=True)) * mask    # [bb, K]

            agg = jnp.zeros((bb, H), jnp.float32)
            for k in range(K):
                agg = agg + z[:, k, :] * attn[:, k:k + 1]
            g = agg + h @ w_self_ref[...]
        g = jnp.maximum(g + b_last_ref[...], 0.0)

        # ---- risk head: MLP([g ; feats]) with the concat pre-split ----
        w0g_ref, w0f_ref, b0_ref = mlp_refs[0:3]
        y = g @ w0g_ref[...] + feats @ w0f_ref[...] + b0_ref[...]
        for i in range(n_mlp_extra):
            wi_ref = mlp_refs[3 + 2 * i]
            bi_ref = mlp_refs[4 + 2 * i]
            y = jnp.maximum(y, 0.0) @ wi_ref[...] + bi_ref[...]
        out_ref[...] = y[:, 0].astype(out_ref.dtype)

    return kernel


def flatten_stage2_params(params, gnn_type: str):
    """Extract the stage-2-relevant leaves of an ``lnn_init`` pytree in the
    kernel's positional argument order.

    Stage-1 self-transform layers stack into ``[L-1, H, H]`` (hidden width is
    constant), biases/embedding rows become ``[1, H]`` so every ref is >= 2-D,
    and the MLP's first weight splits at row H into the ``g_out`` block and
    the raw-feature block.  An ``hgt`` model has its own body and order:
    :func:`flatten_hgt_stage2_params`.
    """
    from repro.core.graph import EdgeType, NodeType

    if gnn_type == "hgt":
        return flatten_hgt_stage2_params(params)
    h = params["last"]["w_self"].shape[0]
    flat = [
        params["input"]["w"],
        params["input"]["b"][None, :],
        params["type_emb"][NodeType.ORDER][None, :],
        jnp.stack([lyr["w_self"] for lyr in params["gnn"]]),
        jnp.stack([lyr["b"] for lyr in params["gnn"]]),
    ]
    if "typed" in params:
        # Heterogeneous models: per-type entity tower blocks ride along
        # right after the stage-1 stacks (order is part of the kernel ABI).
        flat += [params["typed"]["tower_w"], params["typed"]["tower_b"]]
    p = params["last"]
    if gnn_type == "gcn":
        flat += [p["w_self"], p["w_nbr"][EdgeType.ENTITY_TO_ORDER], p["b"][None, :]]
    elif gnn_type == "sage":
        flat += [p["w_self"], p["w_nbr"], p["b"][None, :]]
    elif gnn_type == "gat":
        flat += [p["w_self"], p["b"][None, :], p["w"],
                 p["a_src"][:, None], p["a_dst"][:, None],
                 p["a_et"][EdgeType.ENTITY_TO_ORDER][None, None]]
    else:
        raise ValueError(f"unknown gnn_type {gnn_type}")
    mlp = params["mlp"]
    w0 = mlp[0]["w"]
    flat += [w0[:h], w0[h:], mlp[0]["b"][None, :]]
    for layer in mlp[1:]:
        flat += [layer["w"], layer["b"][None, :]]
    return tuple(flat)


@functools.partial(
    jax.jit, static_argnames=("gnn_type", "block_b", "interpret", "typed"))
def stage2_score_pallas(entity_emb, emb_mask, order_feats, flat,
                        gnn_type: str = "gcn", block_b: int = 128,
                        interpret: bool = True, slot_type=None,
                        typed: bool = False):
    """Fused online stage-2 scoring: ``(emb [B,K,H], mask [B,K], feats [B,F])
    -> logits [B]``.  ``flat`` comes from :func:`flatten_stage2_params`.

    ``typed=True`` selects the heterogeneous kernel variant: ``slot_type``
    (int32 ``[B, K]`` entity-type codes, -1 for padding/untyped slots) rides
    as a fourth data input and ``flat`` carries the two extra tower refs.
    With ``typed=False`` the call is byte-identical to the homogeneous
    kernel — same inputs, same trace, same jit cache key.
    """
    b, k, hdim = entity_emb.shape
    f = order_feats.shape[1]
    bb = _bucket_block(b, block_b)
    grid = (ceil_div(b, bb),)

    n_tower = flat[3].shape[0]
    n_typed = 2 if typed else 0
    n_types = flat[5].shape[0] if typed else 0
    n_fixed = (11 if gnn_type == "gat" else 8) + n_typed
    n_mlp_extra = (len(flat) - n_fixed - 3) // 2

    def _full(a):
        nd = a.ndim
        return pl.BlockSpec(a.shape, lambda i, _nd=nd: (0,) * _nd)

    in_specs = [
        pl.BlockSpec((bb, k, hdim), lambda i: (i, 0, 0)),
        pl.BlockSpec((bb, k), lambda i: (i, 0)),
        pl.BlockSpec((bb, f), lambda i: (i, 0)),
    ]
    data = [entity_emb, emb_mask, order_feats]
    if typed:
        # codes ride as [B, K, 1] so the per-type select broadcasts over
        # lanes: Mosaic cannot reshape a [bb, K] mask to [bb, K, 1]
        in_specs.append(pl.BlockSpec((bb, k, 1), lambda i: (i, 0, 0)))
        data.append(slot_type[..., None])
    in_specs += [_full(a) for a in flat]

    return pl.pallas_call(
        _make_stage2_kernel(gnn_type, n_tower, n_mlp_extra,
                            typed=typed, n_types=n_types),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((b,), jnp.float32),
        interpret=interpret,
    )(*data, *flat)


# ---------------------------------------------------------------------------
# HGT: its own body and launch, so the gcn / gat / sage ones stay as they are
# ---------------------------------------------------------------------------

def _make_hgt_stage2_kernel(n_tower: int, n_slots: int, n_mlp_extra: int):
    """The ``hgt`` body: order tower (each stage-1 layer reduces to
    ``LN(alpha * b_A + (1 - alpha) h)``: an order has no stage-1 in-edges),
    RTE added to each slot's row, the final hop's multi-head attention over
    the slots (relation ENTITY_TO_ORDER: keys and messages by the entity
    type's K/M linear with W^ATT / W^MSG folded in, queries by the order
    type's Q), the order type's gated, normalised update, and the MLP head.
    The slot loop is static, as in the other bodies; per-head sums and the
    head-to-channel broadcast are matmuls with a 0/1 indicator."""
    from repro.core.layers import gelu, layer_norm

    def kernel(*refs):
        with jax.default_matmul_precision("highest"):
            body(*refs)

    def body(*refs):
        emb_ref, mask_ref, feats_ref, base_ref = refs[0:4]
        (w_in_ref, b_in_ref, type_ref, tc_ref, tk_ref, tg_ref, tb_ref,
         rw_ref, rb_ref, wk_ref, bk_ref, wm_ref, bm_ref, wq_ref, bq_ref,
         sc_ref, ind_ref, indt_ref, wa_ref, ba_ref, al_ref, g_ref,
         b_ref) = refs[4:27]
        mlp_refs = refs[27:-1]
        out_ref = refs[-1]

        emb = emb_ref[...].astype(jnp.float32)      # [bb, K, H]
        mask = mask_ref[...].astype(jnp.float32)    # [bb, K]
        feats = feats_ref[...].astype(jnp.float32)  # [bb, F]
        bb, K, H = emb.shape

        h = feats @ w_in_ref[...] + b_in_ref[...] + type_ref[...]
        h = jnp.maximum(h, 0.0)
        for li in range(n_tower):
            h = layer_norm(tc_ref[li] + tk_ref[li] * h, tg_ref[li], tb_ref[li])

        base = base_ref[...].reshape(bb * K, H)
        hs = emb.reshape(bb * K, H) + base @ rw_ref[...] + rb_ref[...]
        k = (hs @ wk_ref[...] + bk_ref[...]).reshape(bb, K, H)
        m = (hs @ wm_ref[...] + bm_ref[...]).reshape(bb, K, H)
        q = h @ wq_ref[...] + bq_ref[...]                         # [bb, H]
        ind, sc = ind_ref[...], sc_ref[...]
        logits = []
        for s in range(n_slots):
            lg = ((k[:, s, :] * q) @ ind) * sc                      # [bb, nh]
            logits.append(jnp.where(mask[:, s:s + 1] > 0, lg, -1e30))
        mx = logits[0]
        for lg in logits[1:]:
            mx = jnp.maximum(mx, lg)
        es = [jnp.exp(lg - mx) for lg in logits]
        tot = es[0]
        for e in es[1:]:
            tot = tot + e
        agg = jnp.zeros((bb, H), jnp.float32)
        for s in range(n_slots):
            attn = es[s] / tot * mask[:, s:s + 1]
            agg = agg + (attn @ indt_ref[...]) * m[:, s, :]
        alpha = al_ref[...]
        g = layer_norm(alpha * (gelu(agg) @ wa_ref[...] + ba_ref[...])
                       + (1.0 - alpha) * h, g_ref[...], b_ref[...])

        w0g_ref, w0f_ref, b0_ref = mlp_refs[0:3]
        y = g @ w0g_ref[...] + feats @ w0f_ref[...] + b0_ref[...]
        for i in range(n_mlp_extra):
            y = (jnp.maximum(y, 0.0) @ mlp_refs[3 + 2 * i][...]
                 + mlp_refs[4 + 2 * i][...])
        out_ref[...] = y[:, 0].astype(out_ref.dtype)

    return kernel


def flatten_hgt_stage2_params(params):
    """The ``hgt`` body's weights in its argument order: the order tower's
    per-layer constants (``alpha * b_A``, ``1 - alpha``, gamma, beta of the
    order type, each ``[L-1, H]``), the last layer's RTE linear, its K and M
    linears of the entity type with W^ATT / W^MSG of ENTITY_TO_ORDER folded
    in, the order type's Q, the logit scale ``mu / sqrt(d_k)``, the head
    indicator and its transpose, the order type's A linear, gate, gamma and
    beta, then the MLP head as in :func:`flatten_stage2_params`."""
    from repro.core.graph import EdgeType, NodeType
    from repro.core.layers import hgt_folded_kv, hgt_num_heads
    from repro.kernels.hgt import head_indicator

    o, r = NodeType.ORDER, EdgeType.ENTITY_TO_ORDER
    p = params["last"]
    h = p["rte"]["w"].shape[0]
    nh = hgt_num_heads(p)

    def alpha(lp):
        return jax.nn.sigmoid(lp["skip"][o])

    gnn = params["gnn"]
    ones = jnp.ones((1, h), jnp.float32)
    wk, bk, wm, bm = hgt_folded_kv(p, (r,))
    ind = head_indicator(h, nh)
    flat = [
        params["input"]["w"],
        params["input"]["b"][None, :],
        params["type_emb"][o][None, :],
        jnp.stack([alpha(lp) * lp["a"]["b"][o] for lp in gnn]),
        jnp.stack([(1.0 - alpha(lp)) * ones[0] for lp in gnn]),
        jnp.stack([lp["ln_g"][o] for lp in gnn]),
        jnp.stack([lp["ln_b"][o] for lp in gnn]),
        p["rte"]["w"], p["rte"]["b"][None, :],
        wk[0], bk, wm[0], bm,
        p["q"]["w"][o], p["q"]["b"][o][None, :],
        (p["mu"][r] / jnp.sqrt(jnp.float32(h // nh)))[None, :],
        ind, ind.T,
        p["a"]["w"][o], p["a"]["b"][o][None, :],
        alpha(p) * ones,
        p["ln_g"][o][None, :], p["ln_b"][o][None, :],
    ]
    mlp = params["mlp"]
    w0 = mlp[0]["w"]
    flat += [w0[:h], w0[h:], mlp[0]["b"][None, :]]
    for layer in mlp[1:]:
        flat += [layer["w"], layer["b"][None, :]]
    return tuple(flat)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def stage2_score_hgt_pallas(entity_emb, emb_mask, order_feats, slot_dt, flat,
                            block_b: int = 128, interpret: bool = True):
    """Fused online stage-2 scoring of an HGT model: ``(emb [B,K,H],
    mask [B,K], feats [B,F], slot_dt [B,K]) -> logits [B]``; ``flat`` from
    :func:`flatten_hgt_stage2_params`.  The sinusoid ``Base(slot_dt)`` is
    computed here, on the device, and rides into the kernel as a
    ``[B, K, H]`` input."""
    from repro.core.layers import rte_base

    b, k, hdim = entity_emb.shape
    f = order_feats.shape[1]
    bb = _bucket_block(b, block_b)
    n_tower = flat[3].shape[0]
    n_mlp_extra = (len(flat) - 23 - 3) // 2

    def _full(a):
        nd = a.ndim
        return pl.BlockSpec(a.shape, lambda i, _nd=nd: (0,) * _nd)

    in_specs = [
        pl.BlockSpec((bb, k, hdim), lambda i: (i, 0, 0)),
        pl.BlockSpec((bb, k), lambda i: (i, 0)),
        pl.BlockSpec((bb, f), lambda i: (i, 0)),
        pl.BlockSpec((bb, k, hdim), lambda i: (i, 0, 0)),
    ] + [_full(a) for a in flat]
    return pl.pallas_call(
        _make_hgt_stage2_kernel(n_tower, k, n_mlp_extra),
        grid=(ceil_div(b, bb),),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((b,), jnp.float32),
        interpret=interpret,
    )(entity_emb, emb_mask, order_feats, rte_base(slot_dt, hdim), *flat)
