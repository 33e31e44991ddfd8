"""Family dispatch: one uniform interface over every architecture family.

    api = model_api(cfg)
    api.param_specs() / api.init_params(key)
    api.loss(params, batch)
    api.prefill(params, batch) -> (logits, state)
    api.decode_step(params, state, tokens) -> (logits, state)
    api.input_specs(shape) -> batch of ShapeDtypeStructs (+ logical shardings)
    api.decode_state_specs(shape) -> decode-state ParamSpecs
    api.make_decode_state(shape) -> all-zeros decode state
    api.slot_slice / slot_update / slot_reset -> per-slot state surgery
        (continuous batching: one batch row is admitted/evicted without
        recomputing the rest of the batch)
    api.counts_routes / counting_routes -> a decode state that counts the
        expert routing of its steps (hybrids with expert layers)
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from . import encdec, lm, ssm
from .shardlib import ParamSpec, init_param_tree

Params = Dict[str, Any]

_is_spec = lambda x: isinstance(x, ParamSpec)


@dataclasses.dataclass(frozen=True)
class BatchSpec:
    """ShapeDtypeStruct + logical axes for one batch input."""

    shape: Tuple[int, ...]
    dtype: Any
    logical: Tuple[Optional[str], ...]

    def struct(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)


def _token_batch(b: int, s: int, with_labels: bool) -> Dict[str, BatchSpec]:
    out = {"tokens": BatchSpec((b, s), jnp.int32, ("batch", None))}
    if with_labels:
        out["labels"] = BatchSpec((b, s), jnp.int32, ("batch", None))
    return out


@dataclasses.dataclass
class ModelAPI:
    cfg: ModelConfig
    #: Execution backend for the model's dense GEMMs (a ``repro.backend``
    #: name or instance); ``None`` keeps the surrounding scope's backend
    #: (usually the zero-overhead ``ideal`` XLA path).
    backend: Any = None

    def __post_init__(self) -> None:
        if self.backend is not None:
            # resolve a name to ONE instance up front: per-call resolution
            # would rebuild the device every step and strand its telemetry
            from ..backend import get_backend
            self.backend = get_backend(self.backend)

    def _scope(self):
        """Active-backend scope for model steps.

        Entered per call so any (re)trace sees this API's backend.  Routing
        binds at trace time: jit wrappers must not be shared across APIs
        with different backends (each ``ServeEngine`` builds its own)."""
        if self.backend is None:
            return contextlib.nullcontext()
        from ..backend import use_backend
        return use_backend(self.backend)

    # ---- params --------------------------------------------------------------

    def param_specs(self) -> Params:
        f = self.cfg.family
        if f in ("dense", "moe", "vlm"):
            return lm.param_specs(self.cfg)
        if f == "ssm":
            return ssm.rwkv6_param_tree(self.cfg)
        if f == "hybrid":
            return ssm.hybrid_program(self.cfg).param_tree(self.cfg)
        if f == "encdec":
            return encdec.param_specs(self.cfg)
        raise ValueError(f"unknown family {f}")

    def init_params(self, key: jax.Array) -> Params:
        return init_param_tree(key, self.param_specs())

    # ---- steps ---------------------------------------------------------------

    def loss(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        f = self.cfg.family
        with self._scope():
            if f in ("dense", "moe", "vlm"):
                return lm.loss_fn(params, batch, self.cfg)
            if f == "ssm":
                return ssm.rwkv6_loss(params, batch, self.cfg)
            if f == "hybrid":
                return ssm.hybrid_program(self.cfg).loss(params, batch,
                                                         self.cfg)
            if f == "encdec":
                return encdec.loss_fn(params, batch, self.cfg)
        raise ValueError(f)

    def prefill(self, params: Params, batch: Dict[str, jax.Array],
                max_len: Optional[int] = None):
        f = self.cfg.family
        with self._scope():
            if f in ("dense", "moe", "vlm"):
                return lm.prefill(params, batch, self.cfg, max_len)
            if f == "encdec":
                return encdec.prefill(params, batch, self.cfg, max_len)
        raise NotImplementedError(
            f"prefill for {f}: SSM/hybrid prompts are absorbed by running "
            "decode_step over the prompt (O(1) state)")

    def decode_step(self, params: Params, state: Params, tokens: jax.Array):
        f = self.cfg.family
        with self._scope():
            if f in ("dense", "moe", "vlm"):
                return lm.decode_step(params, state, tokens, self.cfg)
            if f == "ssm":
                return ssm.rwkv6_decode_step(params, state, tokens, self.cfg)
            if f == "hybrid":
                return ssm.hybrid_program(self.cfg).decode_step(
                    params, state, tokens, self.cfg)
            if f == "encdec":
                return encdec.decode_step(params, state, tokens, self.cfg)
        raise ValueError(f)

    # ---- specs ---------------------------------------------------------------

    def input_specs(self, shape: ShapeConfig) -> Dict[str, BatchSpec]:
        """Batch stand-ins for one assigned (arch x shape) cell."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        f = cfg.family
        if shape.kind == "decode":
            return {"tokens": BatchSpec((b, 1), jnp.int32, ("batch", None))}
        with_labels = shape.is_train
        if f == "vlm":
            p = min(cfg.frontend_tokens, s // 2)
            batch = {
                "patch_embeds": BatchSpec((b, p, cfg.d_model), jnp.bfloat16,
                                          ("batch", None, None)),
                **_token_batch(b, s - p, with_labels),
            }
            return batch
        if f == "encdec":
            t_enc = max(s // cfg.enc_frames_ratio, 1)
            return {
                "frames": BatchSpec((b, t_enc, cfg.d_model), jnp.bfloat16,
                                    ("batch", None, None)),
                **_token_batch(b, s, with_labels),
            }
        return _token_batch(b, s, with_labels)

    def decode_state_specs(self, shape: ShapeConfig) -> Params:
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        long_ctx = shape.name == "long_500k"
        f = cfg.family
        if f in ("dense", "moe", "vlm"):
            return lm.decode_state_specs(cfg, b, s, long_context=long_ctx)
        if f == "ssm":
            return ssm.rwkv6_state_specs(cfg, b)
        if f == "hybrid":
            return ssm.hybrid_program(cfg).state_specs(
                cfg, b, s, long_context=long_ctx)
        if f == "encdec":
            return encdec.decode_state_specs(cfg, b, s)
        raise ValueError(f)

    # ---- per-slot state surgery (continuous batching) ------------------------
    #
    # Every decode-state leaf carries its logical axes in the spec tree, so the
    # batch ("slot") axis can be located per leaf and one row sliced/scattered
    # with a dynamic_slice — no per-family knowledge, no batch recompute.

    def make_decode_state(self, shape: ShapeConfig) -> Params:
        """All-zeros decode state matching ``decode_state_specs(shape)``."""
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.decode_state_specs(shape), is_leaf=_is_spec)

    def slot_slice(self, shape: ShapeConfig, state: Params,
                   slot: jax.Array) -> Params:
        """Extract batch row ``slot`` of a decode state as a batch-1 state."""
        def take(spec, leaf):
            if "batch" not in spec.logical:
                return leaf
            ax = spec.logical.index("batch")
            return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=ax)
        return jax.tree.map(take, self.decode_state_specs(shape), state,
                            is_leaf=_is_spec)

    def slot_update(self, shape: ShapeConfig, state: Params, slot: jax.Array,
                    sub: Params) -> Params:
        """Scatter a batch-1 sub-state (e.g. a fresh prefill) into row
        ``slot``; every other slot's state is untouched."""
        def put(spec, leaf, s):
            if "batch" not in spec.logical:
                return leaf
            ax = spec.logical.index("batch")
            return jax.lax.dynamic_update_slice_in_dim(
                leaf, s.astype(leaf.dtype), slot, axis=ax)
        return jax.tree.map(put, self.decode_state_specs(shape), state, sub,
                            is_leaf=_is_spec)

    def slot_reset(self, shape: ShapeConfig, state: Params,
                   slot: jax.Array) -> Params:
        """Zero one slot's state (eviction) without recomputing the batch."""
        def zero(spec, leaf):
            if "batch" not in spec.logical:
                return leaf
            ax = spec.logical.index("batch")
            shape1 = leaf.shape[:ax] + (1,) + leaf.shape[ax + 1:]
            return jax.lax.dynamic_update_slice_in_dim(
                leaf, jnp.zeros(shape1, leaf.dtype), slot, axis=ax)
        return jax.tree.map(zero, self.decode_state_specs(shape), state,
                            is_leaf=_is_spec)

    # ---- expert routing counted in the decode state ---------------------------

    @property
    def counts_routes(self) -> bool:
        """Whether a decode state can count the expert routing of its steps
        (a hybrid whose layers hold experts)."""
        return (self.cfg.family == "hybrid"
                and self.cfg.layer_count("experts") > 0)

    @staticmethod
    def counting_routes(state: Params) -> Params:
        """``state`` with a count, from zero, of the routing its decode
        steps make: under ``"routed"``, the (token, held expert) pairs and
        the held experts hit (int32 (2,), on the device).  Slot surgery
        takes the state without it."""
        return {**state, "routed": jnp.zeros((2,), jnp.int32)}


def model_api(cfg: ModelConfig, backend: Any = None) -> ModelAPI:
    return ModelAPI(cfg, backend=backend)
