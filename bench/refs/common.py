"""Building blocks of the plain references, and the comparison readings.

Everything runs in float32 at ``Precision.HIGHEST`` (a TPU float32 matmul
otherwise rounds its inputs to bfloat16).  Each matrix product goes through
an ``mm(x, w)`` callable, so the same forward pass yields the reference
(``mm_f32``) and the control (``mm_int8``: int8 weights per output column,
int8 activations per row, int32 accumulation, as an int8 serving path would
compute it).

A configuration module provides::

    layout(model) -> {name: (shape, dtype, init)} nested as the program's
                     parameter tree ("normal" | "embed" | "ones" | "zeros")
    hidden(params, model, tokens, mm) -> final-norm hidden states (S, d)
"""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
REFS_DIR = Path(__file__).resolve().parent

Leaf = Tuple[Tuple[int, ...], str, str]


def load(config_name: str):
    """The reference module ``bench/refs/<config_name>.py``."""
    path = REFS_DIR / f"{config_name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reference {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_ref_" + "".join(c if c.isalnum() else "_"
                               for c in config_name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def init_params(layout: Dict, key: jax.Array) -> Dict:
    """Weights drawn from ``key``: ``normal`` leaves N(0, 1/fan_in) with
    fan_in the second-last axis, ``embed`` leaves N(0, 0.02^2), norms ones.
    Call under ``jax.jit`` so the whole tree is made on the device at once.
    """
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (shape, dtype, init) in zip(keys, leaves):
        if init == "zeros":
            out.append(jnp.zeros(shape, dtype))
        elif init == "ones":
            out.append(jnp.ones(shape, dtype))
        else:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            scale = 0.02 if init == "embed" else 1.0 / math.sqrt(fan_in)
            out.append((jax.random.normal(k, shape, F32) * scale)
                       .astype(dtype))
    return jax.tree.unflatten(treedef, out)


def shapes(layout: Dict) -> Dict:
    """The layout as ``jax.ShapeDtypeStruct`` leaves."""
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l[0], jnp.dtype(l[1])),
                        layout, is_leaf=_is_leaf)


# ---- matrix products --------------------------------------------------------

def mm_f32(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.matmul(x.astype(F32), w.astype(F32), precision=HIGHEST)


def _quant(a: jax.Array, axis: int):
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True),
                        1e-30) / 127.0
    return jnp.clip(jnp.round(a / scale), -127, 127).astype(jnp.int8), scale


def mm_int8(x: jax.Array, w: jax.Array) -> jax.Array:
    """W8A8: symmetric int8 per output column of ``w`` and per row of
    ``x``, exact int32 accumulation, float32 rescale."""
    xq, sx = _quant(x.astype(F32), -1)
    wq, sw = _quant(w.astype(F32), 0)
    acc = jnp.matmul(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(F32) * sx * sw


MM: Dict[str, Callable] = {"f32": mm_f32, "int8": mm_int8}


# ---- layers -----------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding on (S, heads, d), halves rotated as pairs (i, i+d/2)."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * freqs                   # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x: jax.Array, p: Dict, model: Dict, mm: Callable) -> jax.Array:
    """Causal multi-head attention with grouped K/V heads and RoPE over
    positions 0..S-1.  x: (S, d) -> (S, d)."""
    s = x.shape[0]
    h, kv, dh = model["n_heads"], model["n_kv_heads"], model["d_head"]
    pos = jnp.arange(s)
    q = rope(mm(x, p["wq"]).reshape(s, h, dh), pos, model["rope_theta"])
    k = rope(mm(x, p["wk"]).reshape(s, kv, dh), pos, model["rope_theta"])
    v = mm(x, p["wv"]).reshape(s, kv, dh)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST)
    return mm(o.reshape(s, h * dh), p["wo"])


def mlp(x: jax.Array, p: Dict, model: Dict, mm: Callable) -> jax.Array:
    if model["act"] == "swiglu":
        return mm(jax.nn.silu(mm(x, p["wg"])) * mm(x, p["w1"]), p["w2"])
    return mm(jax.nn.gelu(mm(x, p["w1"])), p["w2"])


# ---- readings ---------------------------------------------------------------

def readings(ref, params: Dict, model: Dict, tokens: jax.Array,
             positions: jax.Array, served: jax.Array,
             control: bool = False) -> Dict[str, jax.Array]:
    """Per served token: the reference's best logit and the logit of the
    served token (``gap = best - served``), at the positions that predicted
    them.  With ``control``, also the gap of the token the int8 control puts
    first at each of those positions.  ``tokens`` is the prompt followed by
    the served tokens but the last, right-padded (every layer is causal, so
    the padding changes nothing before it)."""
    emb = params["embedding"]

    def logits(mm):
        h = ref.hidden(params, model, tokens, mm)[positions]
        return mm(h, emb.T)

    lg = logits(mm_f32)
    best = lg.max(-1)
    out = {"gap": best - jnp.take_along_axis(lg, served[:, None], -1)[:, 0]}
    if control:
        top = logits(mm_int8).argmax(-1)
        out["control_gap"] = best - jnp.take_along_axis(
            lg, top[:, None], -1)[:, 0]
    return out


def bucket(n: int, step: int) -> int:
    return max(step, -(-n // step) * step)


def served_gaps(ref, params: Dict, model: Dict, prompt, served,
                step: int = 512, out_pad: int = 512,
                control: bool = False, fn_cache: Dict = None) -> Dict[str, np.ndarray]:
    """Gaps of one request's served tokens (see :func:`readings`), on
    sequences padded to a multiple of ``step`` so that few shapes compile."""
    prompt, served = list(prompt), list(served)
    n = len(served)
    seq = prompt + served[:-1]
    s = bucket(len(seq), step)
    p = bucket(n, out_pad)
    tokens = np.zeros((s,), np.int32)
    tokens[:len(seq)] = seq
    positions = np.zeros((p,), np.int32)
    positions[:n] = len(prompt) - 1 + np.arange(n)
    targets = np.zeros((p,), np.int32)
    targets[:n] = served
    cache = fn_cache if fn_cache is not None else {}
    key = (id(ref), control)
    if key not in cache:
        cache[key] = jax.jit(
            lambda prm, t, q, y: readings(ref, prm, model, t, q, y, control))
    out = cache[key](params, tokens, positions, targets)
    return {k: np.asarray(v)[:n] for k, v in out.items()}
