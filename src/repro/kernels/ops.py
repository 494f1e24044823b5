"""Jit'd public wrappers for all kernels, with ref-path dispatch.

``use_pallas`` routing policy: on TPU the Pallas path compiles natively; on
the CPU backend (the tests' oracle) Pallas executes via ``interpret=True``.
Any other backend is refused rather than interpreted, so a run that lost
its chip fails instead of silently timing the interpreter.  Model code
calls these wrappers; the sharded dry-run uses the ref path (XLA ops) so the
lowering is backend-independent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.csr_spmm import csr_spmm_pallas
from repro.kernels.edge_softmax import edge_softmax_agg_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gqa_decode import gqa_decode_pallas
from repro.kernels.hgt import hgt_edge_softmax_pallas
from repro.kernels.hgt import hgt_relation_project as hgt_relation_project_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.stage2_score import (
    flatten_stage2_params,
    stage2_score_hgt_pallas,
    stage2_score_pallas,
)


def _interpret() -> bool:
    """False on TPU (native Mosaic), True on CPU (interpreter); raises for
    any other backend."""
    backend = jax.default_backend()
    if backend in ("tpu", "cpu"):
        return backend == "cpu"
    raise RuntimeError(
        f"Pallas kernels run natively on 'tpu' or interpreted on 'cpu'; "
        f"backend {backend!r} is neither")


def csr_spmm(h, nbr_idx, weights, block_n: int = 128, block_h: int = 128):
    return csr_spmm_pallas(h, nbr_idx, weights, block_n=block_n, block_h=block_h,
                           interpret=_interpret())


def edge_softmax_agg(z, s_src, s_dst, nbr_idx, nbr_mask, etype_bias,
                     block_n: int = 128):
    return edge_softmax_agg_pallas(z, s_src, s_dst, nbr_idx, nbr_mask, etype_bias,
                                   block_n=block_n, interpret=_interpret())


def hgt_relation_project(h, w, b, block_n: int = 512):
    return hgt_relation_project_pallas(h, w, b, block_n=block_n,
                                       interpret=_interpret())


def hgt_edge_softmax(q, ke, me, scale, mask, block_n: int = 32):
    return hgt_edge_softmax_pallas(q, ke, me, scale, mask, block_n=block_n,
                                   interpret=_interpret())


def flash_attention(q, k, v, causal: bool = True, window: int | None = None,
                    block_q: int = 128, block_k: int = 128):
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=_interpret())


def gqa_decode(q, k, v, kv_len=None, window: int | None = None, block_k: int = 512):
    return gqa_decode_pallas(q, k, v, kv_len=kv_len, window=window,
                             block_k=block_k, interpret=_interpret())


def ssd_scan(x, dt, a, b, c, d_skip=None, chunk: int = 128):
    return ssd_scan_pallas(x, dt, a, b, c, d_skip=d_skip, chunk=chunk,
                           interpret=_interpret())


def stage2_score(params, gnn_type, entity_emb, emb_mask, order_feats,
                 block_b: int = 128, slot_type=None, slot_dt=None):
    """Fused speed-layer scoring: whole online stage-2 path in one launch.

    Takes the full ``lnn_init`` params pytree; the stage-2-relevant leaves
    are flattened into the kernel's argument order here (cheap — slicing and
    one stack, folded away under jit).  Heterogeneous params (``"typed"`` in
    the pytree) select the typed kernel variant: ``slot_type`` is the int32
    ``[B, K]`` entity-type code per slot (-1 = padding/untyped; defaults to
    all -1 when omitted).  ``gnn_type == "hgt"`` runs its own body with
    ``slot_dt`` (int32 ``[B, K]``, the order's snapshot minus each served
    key's).  Returns logits [B].
    """
    if gnn_type == "hgt":
        return stage2_score_hgt_pallas(
            entity_emb, emb_mask, order_feats, slot_dt,
            flatten_stage2_params(params, gnn_type), block_b=block_b,
            interpret=_interpret())
    typed = "typed" in params
    flat = flatten_stage2_params(params, gnn_type)
    if typed and slot_type is None:
        slot_type = jnp.full(emb_mask.shape, -1, jnp.int32)
    if not typed:
        slot_type = None
    return stage2_score_pallas(entity_emb, emb_mask, order_feats, flat,
                               gnn_type=gnn_type, block_b=block_b,
                               interpret=_interpret(),
                               slot_type=slot_type, typed=typed)


# re-export oracles for convenience
csr_spmm_ref = _ref.csr_spmm_ref
edge_softmax_agg_ref = _ref.edge_softmax_agg_ref
mha_ref = _ref.mha_ref
gqa_decode_ref = _ref.gqa_decode_ref
ssd_scan_ref = _ref.ssd_scan_ref
ssd_chunked_ref = _ref.ssd_chunked_ref
