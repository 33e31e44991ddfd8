"""Pallas TPU kernel: Razor double-sampled matmul.

Main path = int8 x int8 -> int32 (the cheap near-threshold path); shadow
path = f32 (the delayed shadow register).  Per output tile the kernel emits a
mismatch flag (relative Frobenius error > tol) and — like Razor's replay —
*corrects* flagged tiles to the shadow value.  This doubles the multiplier
count exactly as the paper notes for Razor (Sec. II-E); the flags feed
core.precision.PrecisionController (Algorithm 2 on precision tiers).

Grid: (M/bm, N/bn); K is loaded whole per tile (rows fit VMEM for K <= ~4k).

The epilogue fuses the flag reduction: a running int32 count of fired tiles
accumulates across the grid, so callers needing only the totals
(``count_flags=True``) avoid a separate host-side gather over the flag map.
``interpret`` defaults through :func:`repro.kernels.tuning.default_interpret`
and block sizes through :func:`repro.kernels.tuning.select_blocks`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import resolve_interpret, select_blocks, sequential_grid

# Per-tile scalars (flag, rel, the flag count) live whole in scalar memory:
# a (1, 1) vector block breaks the TPU's (8, 128) tiling rule.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _quant_rows(x):
    """Symmetric per-row int8 quantization (row = last axis)."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    return q, scale


def _kernel(a_ref, bt_ref, out_ref, flag_ref, rel_ref, count_ref, *,
            tol: float):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init_count():
        count_ref[0, 0] = 0

    a = a_ref[...].astype(jnp.float32)           # (bm, K)
    bt = bt_ref[...].astype(jnp.float32)         # (bn, K)  (B pre-transposed)
    qa, sa = _quant_rows(a)
    qb, sb = _quant_rows(bt)
    main = jnp.dot(qa, qb.T, preferred_element_type=jnp.float32) * sa * sb.T
    shadow = jnp.dot(a, bt.T, preferred_element_type=jnp.float32)
    err = jnp.sqrt(jnp.sum((main - shadow) ** 2))
    refn = jnp.sqrt(jnp.sum(shadow ** 2)) + 1e-12
    rel = err / refn
    fired = rel > tol
    # fused epilogue: correction + flag + running flag reduction in one pass
    out_ref[...] = jnp.where(fired, shadow, main)
    flag_ref[i, j] = fired.astype(jnp.int32)
    rel_ref[i, j] = rel
    count_ref[0, 0] += fired.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "tol",
                                             "interpret"))
def _razor_matmul_call(a, b, *, tol: float, block_m: int, block_n: int,
                       interpret: bool):
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and m % block_m == 0 and n % block_n == 0
    gm, gn = m // block_m, n // block_n
    bt = b.T                                      # (n, k): rows quantize over k
    kernel = functools.partial(_kernel, tol=tol)
    return pl.pallas_call(
        kernel,
        grid=(gm, gn),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, k), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            _SMEM,
            _SMEM,
            _SMEM,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((gm, gn), jnp.int32),
            jax.ShapeDtypeStruct((gm, gn), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(a, bt)


def razor_matmul(a: jax.Array, b: jax.Array, *, tol: float = 0.05,
                 block_m: Optional[int] = None, block_n: Optional[int] = None,
                 interpret: Optional[bool] = None, count_flags: bool = False):
    """Returns (C f32 (M, N) corrected, flags int32 (gm, gn), rel (gm, gn));
    with ``count_flags=True`` additionally the fused int32 fired-tile total."""
    m, _ = a.shape
    n = b.shape[1]
    if block_m is None or block_n is None:
        bm, bn = select_blocks(m, n)
        block_m = bm if block_m is None else block_m
        block_n = bn if block_n is None else block_n
    interpret = resolve_interpret(interpret)
    c, flags, rel, count = _razor_matmul_call(
        a, b, tol=tol, block_m=block_m, block_n=block_n, interpret=interpret)
    if not count_flags:
        return c, flags, rel
    # in-kernel accumulation needs a sequential grid; on parallel-grid
    # backends (GPU) reduce the flag map on the host instead
    total = count[0, 0] if sequential_grid(interpret) else jnp.sum(flags)
    return c, flags, rel, total
