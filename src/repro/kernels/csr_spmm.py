"""Pallas TPU kernel: weighted neighbor gather-sum (the GCN/SAGE hot loop).

    out[i, :] = sum_d weights[i, d] * h[nbr_idx[i, d], :]

TPU adaptation of the scatter/gather SpMM GPU pattern: Mosaic has no
general row gather from a loaded value, so each grid step turns its node
tile's padded in-neighbor lists into a weighted one-hot block of the
adjacency and hands the gather to the MXU:

    A[i, c] = sum_d weights[i, d] * (nbr_idx[i, d] == c0 + c)   (VPU, d order)
    acc    += A @ h[c0 : c0 + bk]                                (MXU)

The grid is (node tiles, feature tiles, node-column blocks); the last axis
is the reduction, accumulated in a VMEM scratch and written on its final
step, so VMEM use is bounded by the block sizes and not by the graph's node
count.  The matmul runs at ``precision=HIGHEST``: at default precision the
chip would run the f32 product as a single bf16 pass and lose the f32
parity the tests hold it to.  Column blocks that overhang the node count
are zeroed before the matmul (their padding is undefined on the chip).

VMEM budget per program (defaults bn=128, bk=1024, bh<=128, D<=64, f32):
    h block    bk x bh    = 1024*128*4 = 512 KiB  (x2 double-buffered)
    A, iota    bn x bk    = 2 x 512 KiB
    idx/w      bn x D     = 2 x 32 KiB
    acc, out   bn x bh    = 2 x 64 KiB                    << 16 MiB VMEM
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils.padding import ceil_div


def onehot_block(idx, vals, c0, bk: int):
    """``A[i, c] = sum_d vals[i, d] * (idx[i, d] == c0 + c)`` as ``[bn, bk]``
    f32, summed in ``d`` order — the gather-as-matmul operand shared by
    this kernel and ``edge_softmax``."""
    bn, D = idx.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bk), 1) + c0
    a = jnp.zeros((bn, bk), jnp.float32)
    for d in range(D):                     # static: D is the padded degree
        a = a + jnp.where(cols == idx[:, d:d + 1], vals[:, d:d + 1], 0.0)
    return a


def masked_rows(h_ref, c0, n: int):
    """The ``[bk, bh]`` column block as f32, rows at or past ``n`` zeroed."""
    h = h_ref[...].astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, h.shape, 0) + c0
    return jnp.where(rows < n, h, 0.0)


def _make_spmm_kernel(n: int, bk: int):
    def kernel(h_ref, idx_ref, w_ref, out_ref, acc_ref):
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        c0 = k * bk
        a = onehot_block(idx_ref[...], w_ref[...].astype(jnp.float32), c0, bk)
        acc_ref[...] += jnp.dot(a, masked_rows(h_ref, c0, n),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)

        @pl.when(k == pl.num_programs(2) - 1)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_h", "block_k", "interpret"))
def csr_spmm_pallas(h, nbr_idx, weights, block_n: int = 128, block_h: int = 128,
                    block_k: int = 1024, interpret: bool = True):
    n, feat = h.shape
    _, d = nbr_idx.shape
    bn = min(block_n, n)
    bh = min(block_h, feat)
    bk = min(block_k, n)
    grid = (ceil_div(n, bn), ceil_div(feat, bh), ceil_div(n, bk))
    return pl.pallas_call(
        _make_spmm_kernel(n, bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bh), lambda i, j, k: (k, j)),     # h column block
            pl.BlockSpec((bn, d), lambda i, j, k: (i, 0)),      # idx: node tile
            pl.BlockSpec((bn, d), lambda i, j, k: (i, 0)),      # weights
        ],
        out_specs=pl.BlockSpec((bn, bh), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, feat), h.dtype),
        scratch_shapes=[pltpu.VMEM((bn, bh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(h, nbr_idx, weights)
