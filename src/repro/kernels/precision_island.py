"""Pallas TPU kernel: precision-island matmul — each output tile computes at
its assigned tier (0=int4, 1=int8, 2=f32), the MXU analogue of
per-partition V_ccint rails (DESIGN.md Sec. 2b mapping table).

Grid: (M/bm, N/bn); the tier map plays the role of the voltage map produced
by the static scheme; the runtime PrecisionController re-tiers from
razor_matmul flags.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import resolve_interpret


def _quant_rows(x, levels: float):
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / levels
    q = jnp.clip(jnp.round(x / scale), -levels, levels)
    return q, scale


def _kernel(a_ref, bt_ref, tier_ref, out_ref):
    a = a_ref[...].astype(jnp.float32)
    bt = bt_ref[...].astype(jnp.float32)
    tier = tier_ref[pl.program_id(0), pl.program_id(1)]
    f32 = jnp.dot(a, bt.T, preferred_element_type=jnp.float32)
    qa8, sa8 = _quant_rows(a, 127.0)
    qb8, sb8 = _quant_rows(bt, 127.0)
    i8 = jnp.dot(qa8, qb8.T, preferred_element_type=jnp.float32) * sa8 * sb8.T
    qa4, sa4 = _quant_rows(a, 7.0)
    qb4, sb4 = _quant_rows(bt, 7.0)
    i4 = jnp.dot(qa4, qb4.T, preferred_element_type=jnp.float32) * sa4 * sb4.T
    out_ref[...] = jnp.where(tier == 0, i4, jnp.where(tier == 1, i8, f32))


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def _precision_island_call(a, b, tiers, *, block_m: int, block_n: int,
                           interpret: bool) -> jax.Array:
    m, k = a.shape
    _, n = b.shape
    gm, gn = m // block_m, n // block_n
    assert tiers.shape == (gm, gn)
    return pl.pallas_call(
        _kernel,
        grid=(gm, gn),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, k), lambda i, j: (j, 0)),
            # the whole tier map sits in scalar memory: a (1, 1) vector
            # block breaks the TPU's (8, 128) tiling rule
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(a, b.T, tiers.astype(jnp.int32))


def precision_island(a: jax.Array, b: jax.Array, tiers: jax.Array, *,
                     block_m: Optional[int] = None,
                     block_n: Optional[int] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Tiered matmul; block sizes default to the island shape ``tiers``
    implies, ``interpret`` to the platform-aware tuning default."""
    m = a.shape[0]
    n = b.shape[1]
    gm, gn = tiers.shape
    block_m = m // gm if block_m is None else block_m
    block_n = n // gn if block_n is None else block_n
    return _precision_island_call(a, b, tiers, block_m=block_m,
                                  block_n=block_n,
                                  interpret=resolve_interpret(interpret))
