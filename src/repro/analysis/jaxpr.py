"""jaxpr census: inventory every host round-trip on the serving hot path.

Traces each config's ``ModelAPI`` prefill/decode exactly the way
``ServeEngine`` jits them (same batch shapes, same ``use_backend`` scope),
then walks the closed jaxpr — recursing through ``pjit``/``scan``/custom-vjp
sub-jaxprs, multiplying by scan trip counts — and reports per config:

* ``pure_callbacks`` — host round-trips per model call (the exact worklist
  for ROADMAP item 1: every one of these pins serve throughput to
  interpreter speed and blocks sharding);
* ``dots``/``flops`` — dot-op count and a flop estimate from
  ``dot_general`` contraction shapes;
* ``dot_dtypes`` — dtype histogram of dot outputs (precision flow).

``*_static`` variants count jaxpr equations without scan weighting.

The pinned reference counts live in ``census_baseline.json``; CI fails when
any config's callback count rises above its pin, so a new host round-trip
can never land silently.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Tuple

# Small smoke config per family: the census is about *structure* (callback
# and dot counts per model call), which smoke shapes share with full ones.
CENSUS_ARCHS: Tuple[str, ...] = (
    "starcoder2-3b",          # dense
    "llama4-scout-17b-a16e",  # moe (shared expert + top-k router)
    "llava-next-mistral-7b",  # vlm
    "rwkv6-1.6b",             # ssm (decode-only prompt absorption)
    "zamba2-2.7b",            # hybrid
    "seamless-m4t-medium",    # encdec
)

PROMPT_LEN = 8
SLOTS = 2
MAX_LEN = 32


# ---- jaxpr walking ----------------------------------------------------------


def _sub_jaxprs(params: Dict[str, Any]):
    """Yield every Jaxpr/ClosedJaxpr buried in an eqn's params."""
    import jax.extend.core as jcore

    def visit(v):
        if isinstance(v, (jcore.Jaxpr, jcore.ClosedJaxpr)):
            yield v
        elif isinstance(v, (tuple, list)):
            for item in v:
                yield from visit(item)
    for v in params.values():
        yield from visit(v)


def _walk(jaxpr, counts: Dict[str, Any], weight: int = 1) -> None:
    import jax.extend.core as jcore

    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        counts["eqns"] += 1
        if prim == "pure_callback":
            counts["pure_callbacks"] += weight
            counts["pure_callbacks_static"] += 1
        elif prim in ("dot_general", "dot"):
            counts["dots"] += weight
            counts["dots_static"] += 1
            counts["flops"] += weight * _dot_flops(eqn)
            dt = str(eqn.outvars[0].aval.dtype)
            counts["dot_dtypes"][dt] = counts["dot_dtypes"].get(dt, 0) + weight
        sub_weight = weight
        if prim == "scan":
            length = eqn.params.get("length")
            if isinstance(length, int) and length > 0:
                sub_weight = weight * length
        for sub in _sub_jaxprs(eqn.params):
            _walk(sub, counts, sub_weight)


def _dot_flops(eqn) -> int:
    """2 * prod(out shape) * prod(contracting dims) for one dot_general."""
    out_shape = tuple(eqn.outvars[0].aval.shape)
    dnums = eqn.params.get("dimension_numbers")
    contract = 1
    if dnums is not None:
        (lhs_c, _), _ = dnums
        lhs_shape = tuple(eqn.invars[0].aval.shape)
        for ax in lhs_c:
            contract *= lhs_shape[ax]
    return 2 * math.prod(out_shape) * contract


def trace_counts(fn, *args) -> Dict[str, Any]:
    """Counts for one traced callable (args may be ShapeDtypeStructs)."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    counts: Dict[str, Any] = {
        "eqns": 0, "pure_callbacks": 0, "pure_callbacks_static": 0,
        "dots": 0, "dots_static": 0, "flops": 0, "dot_dtypes": {},
    }
    _walk(closed, counts)
    counts["dot_dtypes"] = dict(sorted(counts["dot_dtypes"].items()))
    return counts


# ---- per-config tracing -----------------------------------------------------


def census_config(arch: str, backend: str = "reference", *,
                  smoke: bool = True, prompt_len: int = PROMPT_LEN,
                  slots: int = SLOTS, max_len: int = MAX_LEN
                  ) -> Dict[str, Any]:
    """Trace one config's prefill + decode the way ``ServeEngine`` runs them."""
    import jax
    import jax.numpy as jnp

    from ..configs import get_config
    from ..configs.base import ShapeConfig
    from ..models.api import model_api
    from ..models.shardlib import spec_tree_to_structs

    cfg = get_config(arch, smoke=smoke)
    api = model_api(cfg, backend=backend)
    shape = ShapeConfig("census", max_len, slots, "decode")

    params = spec_tree_to_structs(api.param_specs())
    state = spec_tree_to_structs(api.decode_state_specs(shape))
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32)

    report: Dict[str, Any] = {
        "arch": arch, "family": cfg.family, "backend": backend,
        "decode": trace_counts(api.decode_step, params, state, tokens),
    }

    if cfg.family in ("dense", "moe", "vlm", "encdec"):
        batch: Dict[str, Any] = {
            "tokens": jax.ShapeDtypeStruct((1, prompt_len), jnp.int32)}
        if cfg.family == "encdec":
            t_enc = max_len // cfg.enc_frames_ratio
            batch["frames"] = jax.ShapeDtypeStruct(
                (1, t_enc, cfg.d_model), jnp.bfloat16)
        report["prefill"] = trace_counts(
            lambda p, b: api.prefill(p, b, max_len=max_len), params, batch)
    else:
        report["prefill"] = None  # SSM/hybrid absorb prompts via decode_step
    return report


def census(archs: Iterable[str] = CENSUS_ARCHS,
           backend: str = "reference", **kw) -> Dict[str, Any]:
    return {
        "version": 1,
        "backend": backend,
        "slots": kw.get("slots", SLOTS),
        "max_len": kw.get("max_len", MAX_LEN),
        "prompt_len": kw.get("prompt_len", PROMPT_LEN),
        "configs": {a: census_config(a, backend, **kw) for a in archs},
    }


# ---- CI gate ----------------------------------------------------------------


def check_census(current: Dict[str, Any],
                 baseline: Dict[str, Any]) -> List[str]:
    """Violations (empty list = gate passes).

    The gate is one-sided: callback counts may only *fall* relative to the
    baseline (ROADMAP item 1 is about driving them to zero); a drop is
    reported as stale-baseline advice, not a failure.  Dot counts are pinned
    exactly — a changed dot census means the model graph changed and the
    baseline must be regenerated deliberately.
    """
    problems: List[str] = []
    for arch, base_cfg in baseline.get("configs", {}).items():
        cur_cfg = current.get("configs", {}).get(arch)
        if cur_cfg is None:
            problems.append(f"{arch}: missing from current census")
            continue
        for phase in ("prefill", "decode"):
            base = base_cfg.get(phase)
            cur = cur_cfg.get(phase)
            if base is None and cur is None:
                continue
            if (base is None) != (cur is None):
                problems.append(f"{arch}.{phase}: presence changed "
                                f"(baseline={base is not None}, "
                                f"current={cur is not None})")
                continue
            if cur["pure_callbacks"] > base["pure_callbacks"]:
                problems.append(
                    f"{arch}.{phase}: pure_callbacks rose "
                    f"{base['pure_callbacks']} -> {cur['pure_callbacks']} — "
                    f"a new host round-trip landed on the hot path")
            if cur["dots"] != base["dots"]:
                problems.append(
                    f"{arch}.{phase}: dot count changed "
                    f"{base['dots']} -> {cur['dots']} — regenerate the "
                    f"baseline if the model graph change is intentional")
    return problems


def load_census(path: Path) -> Dict[str, Any]:
    return json.loads(Path(path).read_text())


def write_census(report: Dict[str, Any], path: Path) -> None:
    Path(path).write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")
