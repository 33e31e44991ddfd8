"""Production meshes (spec-mandated shapes) and mesh-aware sharding rules.

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before any jax init).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from ..models.shardlib import Rules, multi_pod_rules, single_pod_rules

# TPU v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_LINK_BW = 50e9                # B/s per link
ICI_LINKS_PER_CHIP = 3            # usable torus links on a 16x16 slice


def _make_mesh(shape: Tuple[int, ...],
               axes: Tuple[str, ...]) -> jax.sharding.Mesh:
    # Auto axes keep the pre-explicit sharding semantics the rules assume
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape: Tuple[int, ...] = (2, 2),
                   axes: Tuple[str, ...] = ("data", "model")) -> jax.sharding.Mesh:
    """Small mesh over however many (fake) devices the test process has."""
    return _make_mesh(shape, axes)


def rules_for_mesh(mesh: jax.sharding.Mesh,
                   long_context: bool = False) -> Rules:
    """Sharding rules for a mesh; long_context drops batch sharding (batch=1)
    and spreads cache sequence dims across every axis."""
    multi = "pod" in mesh.axis_names
    rules = multi_pod_rules(mesh) if multi else single_pod_rules(mesh)
    if long_context:
        table = dict(rules.table)
        table["batch"] = None
        rules = Rules(table, mesh)
    return rules


def tp2d_rules(mesh: jax.sharding.Mesh, long_context: bool = False) -> Rules:
    """Serving weight layout: weights stationary, sharded over EVERY mesh
    axis (256/512-way "2D TP"); activations are small (one token/seq) and get
    psum'd instead of gathering gigabytes of weights per layer (§Perf,
    decode cells).  fsdp resolves to None, tp to the full axis tuple."""
    base = rules_for_mesh(mesh, long_context=long_context)
    table = dict(base.table)
    table["fsdp"] = None
    table["tp"] = tuple(mesh.axis_names)
    return Rules(table, mesh)


def chips(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
