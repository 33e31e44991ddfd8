"""Pallas TPU kernel: chunked Mamba2 SSD recurrence.

Same mapping strategy as wkv6: the per-token state recurrence (ref.ssd) is
computed in chunk-parallel matmul form; the (n, p) cross-chunk state rides in
VMEM scratch across the sequential chunk grid axis.

Grid: (B*H, n_chunks).  B/C are shared across heads (n_groups=1) and arrive
pre-broadcast from ops.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import assert_divides, resolve_interpret, select_chunk

EXP_CLAMP = 30.0


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s0_ref,
            y_ref, sout_ref, state, *, n_chunks: int):
    i, c_idx = pl.program_id(0), pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        state[...] = s0_ref[0]

    x = x_ref[0].astype(jnp.float32)          # (ch, p)
    dt = dt_ref[0].astype(jnp.float32)        # (ch, 1)
    B = b_ref[0].astype(jnp.float32)          # (ch, n)
    C = c_ref[0].astype(jnp.float32)          # (ch, n)
    a = a_ref[i]                              # scalar -exp(A_log)
    D = d_ref[i]                              # scalar
    S = state[...]                            # (n, p)

    ch = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (ch, ch), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (ch, ch), 1)
    da = dt * a                               # (ch, 1) log decay, <= 0
    # Mosaic has no cumsum: the inclusive prefix sum is a lower-triangular
    # matmul, at full f32 precision
    cum = jnp.dot((row >= col).astype(jnp.float32), da,
                  preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST)         # (ch, 1)
    # the same prefix sums as a row: pick the diagonal of the broadcast
    cum_row = jnp.sum(jnp.where(row == col, cum, 0.0), axis=0,
                      keepdims=True)                           # (1, ch)
    scores = jnp.dot(C, B.T, preferred_element_type=jnp.float32)  # (ch, ch)
    decay = jnp.exp(jnp.clip(cum - cum_row, -EXP_CLAMP, EXP_CLAMP))
    W = jnp.where(row >= col, scores * decay, 0.0)             # inclusive
    xdt = x * dt
    y = jnp.dot(W, xdt, preferred_element_type=jnp.float32)
    # inter-chunk: C_t . S_in * exp(cum_t)
    y = y + jnp.dot(C, S, preferred_element_type=jnp.float32) \
        * jnp.exp(jnp.clip(cum, -EXP_CLAMP, 0.0))
    y = y + D * x
    y_ref[0] = y

    # state: S' = S * exp(cum_end) + sum_s exp(cum_end - cum_s) B_s (x) xdt_s
    cum_end = cum[ch - 1, 0]                                   # scalar
    tail = jnp.exp(jnp.clip(cum_end - cum, -EXP_CLAMP, EXP_CLAMP))
    B_tail = B * tail
    state[...] = (S * jnp.exp(jnp.clip(cum_end, -EXP_CLAMP, 0.0))
                  + jnp.dot(B_tail.T, xdt, preferred_element_type=jnp.float32))

    @pl.when(c_idx == n_chunks - 1)
    def _out():
        sout_ref[0] = state[...]


def ssd_chunk(x: jax.Array, dt: jax.Array, A_log: jax.Array, B: jax.Array,
              C: jax.Array, D: jax.Array, state: jax.Array, *,
              chunk: Optional[int] = None, interpret: Optional[bool] = None):
    """x: (b, s, h, p); dt: (b, s, h); A_log, D: (h,); B, C: (b, s, n);
    state: (b, h, n, p).  Returns (y (b, s, h, p), final_state).

    ``chunk=None`` picks the largest preferred chunk dividing the sequence;
    ``interpret=None`` resolves to the platform-aware tuning default."""
    chunk = select_chunk(x.shape[1]) if chunk is None else chunk
    return _ssd_chunk_call(x, dt, A_log, B, C, D, state, chunk=chunk,
                           interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_chunk_call(x: jax.Array, dt: jax.Array, A_log: jax.Array,
                    B: jax.Array, C: jax.Array, D: jax.Array,
                    state: jax.Array, *, chunk: int, interpret: bool):
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert_divides(chunk, s, "ssd_chunk sequence chunk")
    nc = s // chunk
    bh = b * h

    xf = jnp.moveaxis(x.astype(jnp.float32), 2, 1).reshape(bh, s, p)
    dtf = jnp.moveaxis(dt.astype(jnp.float32), 2, 1).reshape(bh, s, 1)
    Bf = jnp.repeat(B.astype(jnp.float32), h, axis=0).reshape(b, h, s, n) \
        .reshape(bh, s, n)
    Cf = jnp.repeat(C.astype(jnp.float32), h, axis=0).reshape(b, h, s, n) \
        .reshape(bh, s, n)
    af = jnp.tile(-jnp.exp(A_log.astype(jnp.float32)), b)  # (bh,)
    df = jnp.tile(D.astype(jnp.float32), b)
    sf = state.reshape(bh, n, p).astype(jnp.float32)

    y, s_out = pl.pallas_call(
        functools.partial(_kernel, n_chunks=nc),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, c: (i, c, 0)),
            # per-head scalars sit whole in scalar memory: a (1,) vector
            # block breaks the TPU's (8, 128) tiling rule
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n, p), lambda i, c: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, n, p), lambda i, c: (i, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, s, p), jnp.float32),
                   jax.ShapeDtypeStruct((bh, n, p), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xf, dtf, Bf, Cf, af, df, sf)
    y = jnp.moveaxis(y.reshape(b, h, s, p), 1, 2)
    return y, s_out.reshape(b, h, n, p)
