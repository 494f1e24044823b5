"""Pallas TPU kernels of HGT's stage-1 layers (``core.layers.hgt_attend``).

Two device programs per layer, each under a stable name the trace shows:

* ``hgt_relation_project``: ``out[p] = h @ w[p] + b[p]`` for a stack of
  ``P`` projections, the K and M linears of each relation's source type
  with that relation's per-head W^ATT / W^MSG folded in.  Every node row
  is projected once per (relation, K or M); grid ``(P, node tiles)``.
* ``hgt_edge_softmax_pallas``: per node tile, the per-head logits
  ``sum_c ke[t, j, c] q[t, c]`` over the head's ``d_k`` channels, scaled
  by ``mu / sqrt(d_k)``, the masked softmax over the ``D`` in-edge slots
  per head, and ``out[t] = sum_j attn[t, j, head(c)] me[t, j, c]``.  The
  per-head channel sums and the head-to-channel broadcast are matmuls with
  a 0/1 indicator ``[H, heads]``, so no lane is sliced.  Its work grows
  with the edges (``N * D * H``), not with ``N^2``: the rows ``ke`` and
  ``me`` are gathered per edge by XLA (``relation * N + src``) before the
  call.

VMEM budget per program of ``hgt_edge_softmax_pallas`` (bn=32, D=32,
H=256, 8 heads, f32):
    ke, me blocks   2 x bn x D x H      = 2 x 1 MiB   (x2 double-buffered)
    scale, mask     bn x D x 128 lanes  = 2 x 512 KiB (x2)
    q, out          bn x H              = 2 x 32 KiB
    products        bn*D x H            = ~3 x 1 MiB            < 16 MiB
and of ``hgt_relation_project`` (bn=512, H=256): h and out blocks 512 KiB
each, one weight 256 KiB, double-buffered ~2.5 MiB.

Every matmul runs at highest precision: the chip's default would run an
f32 product as one bf16 pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils.padding import ceil_div

HIGHEST = jax.lax.Precision.HIGHEST


def head_indicator(h: int, heads: int):
    """``[H, heads]`` 0/1: channel ``c`` belongs to head ``c // (H / heads)``."""
    return (jnp.arange(h)[:, None] // (h // heads)
            == jnp.arange(heads)[None, :]).astype(jnp.float32)


def _project_kernel(h_ref, w_ref, b_ref, out_ref):
    out_ref[...] = (jnp.dot(h_ref[...], w_ref[...], precision=HIGHEST,
                            preferred_element_type=jnp.float32)
                    + b_ref[...]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def hgt_relation_project(h, w, b, block_n: int = 512, interpret: bool = True):
    """``h [N, H]``, ``w [P, H, O]``, ``b [P, O]`` -> ``[P, N, O]``."""
    n, hd = h.shape
    p, _, o = w.shape
    bn = min(block_n, n)
    return pl.pallas_call(
        _project_kernel,
        grid=(p, ceil_div(n, bn)),
        in_specs=[pl.BlockSpec((bn, hd), lambda k, i: (i, 0)),
                  pl.BlockSpec((None, hd, o), lambda k, i: (k, 0, 0)),
                  pl.BlockSpec((None, 1, o), lambda k, i: (k, 0, 0))],
        out_specs=pl.BlockSpec((None, bn, o), lambda k, i: (k, i, 0)),
        out_shape=jax.ShapeDtypeStruct((p, n, o), h.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(h, w, b[:, None, :])


def _edge_softmax_kernel(q_ref, ke_ref, me_ref, sc_ref, mask_ref, ind_ref,
                         indt_ref, out_ref):
    bn, d, hd = ke_ref.shape
    nh = sc_ref.shape[-1]
    ind = ind_ref[...]                                  # [H, heads]
    prod = (ke_ref[...] * q_ref[...]).reshape(bn * d, hd)
    logits = jnp.dot(prod, ind, precision=HIGHEST,
                     preferred_element_type=jnp.float32).reshape(bn, d, nh)
    logits = logits * sc_ref[...]
    mask = mask_ref[...]                                # [bn, D, 1]
    logits = jnp.where(mask > 0, logits, -1e30)
    m = jnp.max(logits, axis=1, keepdims=True)
    e = jnp.exp(logits - m)
    attn = e / jnp.sum(e, axis=1, keepdims=True) * mask   # [bn, D, heads]
    w = jnp.dot(attn.reshape(bn * d, nh), indt_ref[...], precision=HIGHEST,
                preferred_element_type=jnp.float32)        # [bn * D, H]
    msg = (w * me_ref[...].reshape(bn * d, hd)).reshape(bn, d, hd)
    out_ref[...] = jnp.sum(msg, axis=1).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def hgt_edge_softmax_pallas(q, ke, me, scale, mask, block_n: int = 32,
                            interpret: bool = True):
    """``q [N, H]`` queries, ``ke``/``me [N, D, H]`` each in-edge's key and
    message rows, ``scale [N, D, heads]`` the logit scale ``mu / sqrt(d_k)``
    per edge and head, ``mask [N, D]`` -> ``[N, H]``."""
    n, d, hd = ke.shape
    nh = scale.shape[-1]
    bn = min(block_n, n)
    ind = head_indicator(hd, nh)
    edge = pl.BlockSpec((bn, d, hd), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _edge_softmax_kernel,
        grid=(ceil_div(n, bn),),
        in_specs=[pl.BlockSpec((bn, 1, hd), lambda i: (i, 0, 0)),
                  edge, edge,
                  pl.BlockSpec((bn, d, nh), lambda i: (i, 0, 0)),
                  pl.BlockSpec((bn, d, 1), lambda i: (i, 0, 0)),
                  pl.BlockSpec((hd, nh), lambda i: (0, 0)),
                  pl.BlockSpec((nh, hd), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bn, hd), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(q[:, None, :], ke, me, scale, mask[..., None], ind, ind.T)
