"""Pallas TPU kernel: Mamba2 SSD chunked scan (state-space duality).

TPU adaptation of the Mamba2 GPU kernel (arXiv:2405.21060): the SSD
decomposition splits the sequence into chunks; within a chunk the recurrence
is evaluated as a small causal "attention" (dense matmuls — MXU-friendly),
and a [N, P] state matrix is carried *sequentially across chunk grid steps*
in VMEM scratch — exactly where a GPU implementation would use an
inter-block carry.  This keeps every op a dense matmul on (chunk, N, P)
tiles, no scan over single timesteps.

Grid = (batch, heads, num_chunks), chunks innermost/sequential.

Per-program VMEM (chunk Q=128, N=128, P=64, f32):
  x (Q,P) 32 KiB + b,c (Q,N) 2x64 KiB + decay (Q,Q) 64 KiB
  + state (N,P) 32 KiB + out (Q,P) 32 KiB  << 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, out_ref, state_ref, *, chunk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0].astype(jnp.float32)        # [Q, P]
    dt = dt_ref[0, :, 0].astype(jnp.float32)      # [Q]
    a = a_ref[0]                                  # scalar decay rate (this head)
    b = b_ref[0].astype(jnp.float32)              # [Q, N]
    c = c_ref[0].astype(jnp.float32)              # [Q, N]

    seg = dt * a                                   # [Q] log-decay increments
    cum = jnp.cumsum(seg)                          # inclusive
    total = cum[-1]

    # ---- intra-chunk: causal decay-weighted attention ----------------------
    scores = jnp.dot(c, b.T, preferred_element_type=jnp.float32)      # [Q, Q]
    li = cum[:, None]
    lj = cum[None, :]
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iota_j <= iota_i
    decay = jnp.exp(jnp.clip(li - lj, -60.0, 0.0))
    w = jnp.where(causal, scores * decay, 0.0)
    y_intra = jnp.dot(w * dt[None, :], x, preferred_element_type=jnp.float32)

    # ---- inter-chunk: contribution of the carried state --------------------
    state = state_ref[...]                         # [N, P]
    y_inter = jnp.exp(jnp.clip(cum, -60.0, 0.0))[:, None] * jnp.dot(
        c, state, preferred_element_type=jnp.float32
    )
    out_ref[0, :, 0] = (y_intra + y_inter).astype(out_ref.dtype)

    # ---- state update -------------------------------------------------------
    dec_state = jnp.exp(jnp.clip(total - cum, -60.0, 0.0)) * dt       # [Q]
    new_state = jnp.dot((b * dec_state[:, None]).T, x,
                        preferred_element_type=jnp.float32)           # [N, P]
    state_ref[...] = state * jnp.exp(jnp.clip(total, -60.0, 0.0)) + new_state


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x, dt, a, b, c, d_skip=None, chunk: int = 128,
                    interpret: bool = True):
    """x: [B,S,H,P]; dt: [B,S,H]; a: [H]; b,c: [B,S,N].  Returns [B,S,H,P]."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    assert S % chunk == 0, f"seq {S} not divisible by chunk {chunk}"
    nc = S // chunk
    grid = (B, H, nc)

    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b_, h, k_: (b_, k_, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b_, h, k_: (b_, k_, h)),
            pl.BlockSpec((1,), lambda b_, h, k_: (h,)),
            pl.BlockSpec((1, chunk, N), lambda b_, h, k_: (b_, k_, 0)),
            pl.BlockSpec((1, chunk, N), lambda b_, h, k_: (b_, k_, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, P), lambda b_, h, k_: (b_, k_, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, a, b, c)
    if d_skip is not None:
        y = y + (x.astype(jnp.float32) * d_skip[None, None, :, None]).astype(y.dtype)
    return y
