"""Pallas TPU kernel: GAT edge softmax + weighted aggregation.

    logits[i,d] = leaky_relu(s_src[nbr_idx[i,d]] + s_dst[i] + etype_bias[i,d])
    attn        = softmax over valid d  (masked by nbr_mask)
    out[i, :]   = sum_d attn[i,d] * z[nbr_idx[i,d], :]

The scalar gather ``s_src[nbr_idx]`` is one XLA gather in the wrapper
(Mosaic lowers only 2-D gathers in-kernel); the kernel then owns the
softmax and the row aggregation, which it runs as the same weighted one-hot
``A @ z`` block matmul as ``csr_spmm`` (``A`` built from ``attn`` instead of
the degree weights).  Grid: (node tiles, node-column blocks), the column
axis a reduction accumulated in VMEM; softmax is recomputed per column
block (``bn x D``, negligible next to the one-hot build) in f32 with the
usual max-subtraction.  GNN hidden dims here are <= 256, so every block
carries the full feature width.

VMEM budget per program (defaults bn=128, bk=1024, H<=256, D<=64, f32):
    z block    bk x H     = 1024*256*4 = 1 MiB  (x2 double-buffered)
    A, iota    bn x bk    = 2 x 512 KiB
    logits     bn x D     = a few x 32 KiB
    acc, out   bn x H     = 2 x 128 KiB                   << 16 MiB VMEM
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.csr_spmm import masked_rows, onehot_block
from repro.utils.padding import ceil_div


def _make_edge_softmax_kernel(n: int, bk: int):
    def kernel(z_ref, ssrc_ref, sdst_ref, idx_ref, mask_ref, bias_ref,
               out_ref, acc_ref):
        k = pl.program_id(1)

        @pl.when(k == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        mask = mask_ref[...]                                   # [bn, D]
        logits = (ssrc_ref[...] + sdst_ref[...] + bias_ref[...]).astype(
            jnp.float32)
        logits = jnp.where(logits >= 0, logits, 0.2 * logits)  # leaky relu
        logits = jnp.where(mask > 0, logits, -1e9)
        m = jnp.max(logits, axis=-1, keepdims=True)
        e = jnp.exp(logits - m)
        attn = (e / jnp.sum(e, axis=-1, keepdims=True)) * mask

        c0 = k * bk
        a = onehot_block(idx_ref[...], attn, c0, bk)
        acc_ref[...] += jnp.dot(a, masked_rows(z_ref, c0, n),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)

        @pl.when(k == pl.num_programs(1) - 1)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("block_n", "block_k", "interpret"))
def edge_softmax_agg_pallas(z, s_src, s_dst, nbr_idx, nbr_mask, etype_bias,
                            block_n: int = 128, block_k: int = 1024,
                            interpret: bool = True):
    n, feat = z.shape
    _, d = nbr_idx.shape
    bn = min(block_n, n)
    bk = min(block_k, n)
    grid = (ceil_div(n, bn), ceil_div(n, bk))
    ssrc = jnp.take(s_src, nbr_idx, axis=0)                    # [N, D]
    tile = pl.BlockSpec((bn, d), lambda i, k: (i, 0))
    return pl.pallas_call(
        _make_edge_softmax_kernel(n, bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, feat), lambda i, k: (k, 0)),     # z column block
            tile,                                              # s_src[nbr_idx]
            pl.BlockSpec((bn, 1), lambda i, k: (i, 0)),        # s_dst
            tile, tile, tile,                                  # idx, mask, bias
        ],
        out_specs=pl.BlockSpec((bn, feat), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, feat), z.dtype),
        scratch_shapes=[pltpu.VMEM((bn, feat), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(z, ssrc, s_dst[:, None], nbr_idx, nbr_mask, etype_bias)
