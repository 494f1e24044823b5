"""Pallas TPU kernel: flash attention (prefill) with GQA + causal/sliding-window.

Standard online-softmax tiling: grid = (batch, q_heads, q_tiles, k_tiles)
with the k dimension innermost and *sequential* ("arbitrary" dimension
semantics on TPU), carrying the running max / denominator / accumulator in
f32 VMEM scratch across k steps.  The output tile is written once, at the
last k step.

GQA: the k/v BlockSpec index-maps q-head h to kv-head h // (Hq // Hkv), so
no repeated K/V materialization happens — each q head streams the shared
kv head's tiles.

VMEM per program (bq=bk=128, Dh=128, f32 accum):
  q 64 KiB + k 64 KiB + v 64 KiB + acc 64 KiB + m/l 1 KiB  << 16 MiB.
Block sizes are multiples of (8, 128) so all matmuls are MXU-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils.padding import ceil_div

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref,
                  *, scale, causal, window, bq, bk, sk, sq):
    j = pl.program_id(3)
    nk = pl.num_programs(3)
    i = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                          # [bq, Dh]
    k = k_ref[0, 0]                          # [bk, Dh]
    v = v_ref[0, 0]                          # [bk, Dh]

    logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    # absolute positions; q rows are aligned to the END of the kv sequence
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (sk - sq)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.max(logits, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        out_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(
            out_ref.dtype
        )


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention_pallas(q, k, v, causal: bool = True, window: int | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = True):
    b, hq, sq, dh = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    grid = (b, hq, ceil_div(sq, bq), ceil_div(sk, bk))
    scale = dh ** -0.5

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, sk=sk, sq=sq,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h, i, j, rep=rep: (b_, h // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h, i, j, rep=rep: (b_, h // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max  m
            pltpu.VMEM((bq,), jnp.float32),      # denominator  l
            pltpu.VMEM((bq, dh), jnp.float32),   # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
