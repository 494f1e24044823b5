"""Pallas TPU kernel: single-token GQA decode attention (flash-decoding).

The speed-layer analogue of the paper's lambda split: the KV cache is the
precomputed batch artifact, the kernel performs the per-request online step.

    out[b, hq, :] = softmax(q[b, hq] · K[b, kv(hq)] / sqrt(D)) @ V[b, kv(hq)]

Grid = (batch, kv_heads, kv_tiles); the kv dimension is innermost and
sequential, carrying running max / denom / accumulator per q-head-group in
VMEM scratch (classic flash-decoding).  All q heads sharing one kv head are
processed together as a [rep, Dh] block so the kv tile is streamed once —
the GQA bandwidth saving is structural, not a copy.

``kv_len`` masks the ragged cache tail; ``window`` implements sliding-window
decode (only the last ``window`` valid positions attend) for SWA archs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils.padding import ceil_div

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref,
                   *, scale, bk, window):
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                      # [rep, Dh] — q heads of this kv group
    k = k_ref[0, 0]                      # [bk, Dh]
    v = v_ref[0, 0]                      # [bk, Dh]
    kv_len = len_ref[0]

    logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [rep, bk]
    pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = pos < kv_len
    if window is not None:
        valid &= pos >= kv_len - window
    logits = jnp.where(valid, logits, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    p = jnp.exp(logits - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        out_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def gqa_decode_pallas(q, k, v, kv_len=None, window: int | None = None,
                      block_k: int = 512, interpret: bool = True):
    b, hq, dh = q.shape
    hkv, s = k.shape[1], k.shape[2]
    rep = hq // hkv
    bk = min(block_k, s)
    if kv_len is None:
        kv_len = jnp.full((b,), s, jnp.int32)
    q4 = q.reshape(b, hkv, rep, dh)
    grid = (b, hkv, ceil_div(s, bk))
    scale = dh ** -0.5

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bk=bk, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b_, h, j: (b_,)),
            pl.BlockSpec((1, 1, rep, dh), lambda b_, h, j: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h, j: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h, j: (b_, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, dh), lambda b_, h, j: (b_, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), q4, k, v)
    return out.reshape(b, hq, dh)
