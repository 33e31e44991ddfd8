"""Pallas TPU kernel: voltage-island partitioned systolic matmul with
timing-fault injection + Razor flags (the paper's partitioned MAC array
mapped onto MXU tiles; DESIGN.md Sec. 2b).

Grid: (M/bm, N/bn, K/bk); each (i, j) output tile is one 'FPGA partition
cell' carrying a rail voltage v_map[i, j] and a minimum safe voltage
v_safe[i, j].  Under-volted tiles corrupt their accumulator low bits (the
timing-failure model shared with ref.corrupt_low_bits) and raise a flag —
exactly the per-partition Razor flag the runtime scheme consumes.

``interpret`` defaults through :func:`repro.kernels.tuning.default_interpret`
(compiled everywhere a Mosaic backend exists, interpreted only on CPU);
``block_m``/``block_n`` default to the partition-cell shape dictated by
``v_map`` and ``block_k`` to the tuning table's preference.  The epilogue
optionally fuses the Razor flag reduction: with ``count_flags=True`` a
running int32 total of fired tiles is accumulated in-kernel, so callers that
only need "how many partitions failed" skip the host-side flag gather.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import resolve_interpret, select_blocks, sequential_grid

# Per-tile scalars (rails, flags, the flag count) live whole in scalar
# memory: a (1, 1) vector block breaks the TPU's (8, 128) tiling rule.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _kernel(a_ref, b_ref, vmap_ref, vsafe_ref, out_ref, flag_ref, count_ref,
            acc_ref, *, keep_bits: int, n_k: int):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0) & (k == 0))
    def _init_count():
        count_ref[0, 0] = 0

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32),
                            b_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        acc = acc_ref[...]
        fail = vmap_ref[i, j] < vsafe_ref[i, j]
        # mantissa truncation = low accumulator bits missing the clock edge
        bits = jax.lax.bitcast_convert_type(acc, jnp.uint32)
        mask = jnp.uint32(0xFFFFFFFF) << jnp.uint32(23 - keep_bits)
        corrupted = jax.lax.bitcast_convert_type(bits & mask, jnp.float32)
        out_ref[...] = jnp.where(fail, corrupted, acc)
        flag_ref[i, j] = fail.astype(jnp.int32)
        # fused Razor flag reduction: running total over all (i, j) tiles
        count_ref[0, 0] += fail.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "keep_bits", "interpret"))
def _systolic_mac_call(a, b, v_map, v_safe, *, block_m: int, block_n: int,
                       block_k: int, keep_bits: int, interpret: bool):
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    gm, gn, gk = m // block_m, n // block_n, k // block_k
    assert v_map.shape == (gm, gn) == v_safe.shape

    kernel = functools.partial(_kernel, keep_bits=keep_bits, n_k=gk)
    return pl.pallas_call(
        kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            _SMEM,
            _SMEM,
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
            _SMEM,
            _SMEM,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((gm, gn), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(a, b, v_map.astype(jnp.float32), v_safe.astype(jnp.float32))


def systolic_mac(a: jax.Array, b: jax.Array, v_map: jax.Array,
                 v_safe: jax.Array, *, block_m: Optional[int] = None,
                 block_n: Optional[int] = None, block_k: Optional[int] = None,
                 keep_bits: int = 8, interpret: Optional[bool] = None,
                 count_flags: bool = False):
    """C = a @ b with per-tile voltage-island fault semantics.

    a: (M, K); b: (K, N); v_map/v_safe: (M/bm, N/bn).
    Returns (C f32 (M, N), flags int32 (M/bm, N/bn)); with
    ``count_flags=True`` additionally the in-kernel int32 total of fired
    tiles.  ``block_m``/``block_n`` default to the cell shape ``v_map``
    implies; ``block_k`` comes from the tuning table.
    """
    m, k = a.shape
    n = b.shape[1]
    gm, gn = v_map.shape
    block_m = m // gm if block_m is None else block_m
    block_n = n // gn if block_n is None else block_n
    if block_k is None:
        block_k = select_blocks(m, n, k)[2]
    interpret = resolve_interpret(interpret)
    c, flags, count = _systolic_mac_call(
        a, b, v_map, v_safe, block_m=block_m, block_n=block_n,
        block_k=block_k, keep_bits=keep_bits, interpret=interpret)
    if not count_flags:
        return c, flags
    # the in-kernel accumulator relies on sequential grid execution; on
    # parallel-grid backends (GPU) reduce the flag map on the host instead
    total = count[0, 0] if sequential_grid(interpret) else jnp.sum(flags)
    return c, flags, total
