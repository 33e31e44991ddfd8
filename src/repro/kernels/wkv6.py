"""Pallas TPU kernel: chunked RWKV6 (Finch) WKV recurrence.

The per-token recurrence (ref.wkv6) is matmul-poor; this kernel computes the
chunked form — (chunk x chunk) attention-like matmuls on the MXU with the
cross-chunk state carried in VMEM scratch across sequential grid steps — the
standard TPU mapping for linear-attention recurrences (DESIGN.md hardware
adaptation: per-step scans become MXU tiles).

Grid: (B*H, n_chunks); chunk axis iterates fastest, so the scratch state is
valid per (b, h) and reset at chunk 0.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import assert_divides, resolve_interpret, select_chunk

EXP_CLAMP = 60.0


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
            state, *, n_chunks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state[...] = s0_ref[0]

    r = r_ref[0].astype(jnp.float32)          # (ch, p)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w_log = w_ref[0].astype(jnp.float32)      # (ch, p), log decay <= 0
    u = u_ref[0].astype(jnp.float32)          # (1, p)
    S = state[...]                            # (p, p)

    ch, p = r.shape
    # Mosaic has no cumsum: the inclusive prefix sum is a lower-triangular
    # matmul, at full f32 precision
    row = jax.lax.broadcasted_iota(jnp.int32, (ch, ch), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (ch, ch), 1)
    lw = jnp.dot((row >= col).astype(jnp.float32), w_log,
                 preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)          # inclusive
    lw_prev = lw - w_log                                       # exclusive
    # centre exponents at half the chunk's total decay so exp() stays in
    # f32 range for any chunk length (the A entries are products
    # exp(lw_prev_t - m) * exp(m - lw_s) = exp(lw_prev_t - lw_s) <= 1)
    lw_end = lw[ch - 1:]                                       # (1, p)
    m = 0.5 * lw_end
    rr = r * jnp.exp(jnp.clip(lw_prev - m, -EXP_CLAMP, EXP_CLAMP))
    kk = k * jnp.exp(jnp.clip(m - lw, -EXP_CLAMP, EXP_CLAMP))
    A = jnp.dot(rr, kk.T, preferred_element_type=jnp.float32)   # (ch, ch)
    A = jnp.where(row > col, A, 0.0)
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)          # (ch, 1)
    y = jnp.dot(A, v, preferred_element_type=jnp.float32)
    y = y + diag * v
    # inter-chunk from carried state (exp(lw_prev) <= 1: no centring needed)
    r_state = r * jnp.exp(jnp.clip(lw_prev, -EXP_CLAMP, 0.0))
    y = y + jnp.dot(r_state, S, preferred_element_type=jnp.float32)

    # state update: S' = diag(prod w) S + sum_s (k_s * decay_to_end) v_s^T
    tail = jnp.exp(jnp.clip(lw_end - lw, -EXP_CLAMP, EXP_CLAMP))
    k_tail = k * tail
    # the per-row decay as a column: pick the diagonal of a broadcast row
    eye = (jax.lax.broadcasted_iota(jnp.int32, (p, p), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (p, p), 1))
    decay_col = jnp.sum(jnp.where(eye, jnp.exp(jnp.clip(lw_end, -EXP_CLAMP,
                                                         0.0)), 0.0),
                        axis=1, keepdims=True)                 # (p, 1)
    S_new = (S * decay_col
             + jnp.dot(k_tail.T, v, preferred_element_type=jnp.float32))
    state[...] = S_new
    y_ref[0] = y

    @pl.when(c == n_chunks - 1)
    def _out():
        sout_ref[0] = state[...]


def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, w_log: jax.Array,
         u: jax.Array, state: jax.Array, *, chunk: Optional[int] = None,
         interpret: Optional[bool] = None):
    """r,k,v,w_log: (b, s, h, p) f32; u: (h, p); state: (b, h, p, p).

    Returns (y (b, s, h, p) f32, final state (b, h, p, p)).

    ``chunk=None`` picks the largest preferred chunk dividing the sequence;
    ``interpret=None`` resolves to the platform-aware tuning default.
    """
    chunk = select_chunk(r.shape[1]) if chunk is None else chunk
    return _wkv6_call(r, k, v, w_log, u, state, chunk=chunk,
                      interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _wkv6_call(r: jax.Array, k: jax.Array, v: jax.Array, w_log: jax.Array,
               u: jax.Array, state: jax.Array, *, chunk: int,
               interpret: bool):
    b, s, h, p = r.shape
    assert_divides(chunk, s, "wkv6 sequence chunk")
    nc = s // chunk
    bh = b * h

    def flat(x):
        return jnp.moveaxis(x, 2, 1).reshape(bh, s, p)     # (bh, s, p)

    rf, kf, vf, wf = (flat(x.astype(jnp.float32)) for x in (r, k, v, w_log))
    uf = jnp.tile(u.astype(jnp.float32), (b, 1))[:, None]  # (bh, 1, p)
    sf = state.reshape(bh, p, p).astype(jnp.float32)

    seq_spec = pl.BlockSpec((1, chunk, p), lambda i, c: (i, c, 0))
    y, s_out = pl.pallas_call(
        functools.partial(_kernel, n_chunks=nc),
        grid=(bh, nc),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, 1, p), lambda i, c: (i, 0, 0)),
                  pl.BlockSpec((1, p, p), lambda i, c: (i, 0, 0))],
        out_specs=[seq_spec,
                   pl.BlockSpec((1, p, p), lambda i, c: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, s, p), jnp.float32),
                   jax.ShapeDtypeStruct((bh, p, p), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((p, p), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, uf, sf)
    y = jnp.moveaxis(y.reshape(b, h, s, p), 1, 2)
    return y, s_out.reshape(b, h, p, p)
