"""Plain float32 reference of granite-4.0-h-small as served (IBM Granite
4.0-H Small, ``granitemoehybrid``; sizes from the configuration file).

Tokens are embedded and multiplied by ``embedding_multiplier``.  Each layer
is the blocks its ``layer_types`` entry lists, in order, each a residual
branch ``x + residual_multiplier * block(RMSNorm(x))``:

* ``mamba2``: in-projection (no bias) to z | x B C | dt, a causal depthwise
  convolution of width ``ssm_conv`` with bias and SiLU over x B C, the
  selective state-space scan with one group of B and C, a gate by SiLU(z),
  RMSNorm over the whole inner width, out-projection;
* ``attention``: causal grouped-query attention with no position encoding,
  scores scaled by ``attention_multiplier``;
* ``experts``: a router over all ``n_experts`` (top ``top_k`` logits, gates
  their softmax), of which the first ``experts_held`` experts, held here,
  add their SwiGLU output times their gate (routes to experts held on other
  chips add nothing, and the gates are not renormalised), plus the shared
  SwiGLU expert.

A final RMSNorm, and logits against the tied embedding divided by
``logits_scaling``: :func:`hidden` returns the final-norm states already
divided.  The scan is written in its quadratic form, from its definition:
``y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<k<=t} dt_k A) dt_s x_s + D x_t``.

Departures from the published model beyond the configuration's cut (the
first ``n_layers`` layers, the first ``experts_held`` experts): none."""

from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

from bench.cost import padded_vocab
from bench.refs.common import HIGHEST, rmsnorm

BF16, F32 = "bfloat16", "float32"
#: Each kind's stack of weights in the parameter tree.
STACK = {"mamba2": "mamba", "attention": "attn", "experts": "moe"}


def dims(model: Dict) -> Dict[str, int]:
    d_inner = model["ssm_expand"] * model["d_model"]
    n = model["ssm_state"]
    heads = d_inner // model["ssm_d_head"]
    return {"d_inner": d_inner, "heads": heads, "state": n,
            "conv_dim": d_inner + 2 * n,
            "in_dim": 2 * d_inner + 2 * n + heads}


def _layers(model: Dict) -> List[List[str]]:
    return [[t] if isinstance(t, str) else list(t)
            for t in model["layer_types"]]


def _count(model: Dict, kind: str) -> int:
    return sum(kind in t for t in _layers(model))


def layout(model: Dict) -> Dict:
    d, m = model["d_model"], dims(model)
    q = model["n_heads"] * model["d_head"]
    kv = model["n_kv_heads"] * model["d_head"]
    lm, la, le = (_count(model, k) for k in STACK)
    e, f, fs = model["experts_held"], model["d_expert"], model["d_shared_expert"]
    # N(0, 1/vocab), not the other configurations' N(0, 0.02^2): times
    # ``embedding_multiplier`` (12) a token's own embedding would outweigh
    # every residual branch, and any model would serve the last token again
    out = {"embedding": ((padded_vocab(model), d), BF16, "normal"),
           "final_norm": ((d,), F32, "ones")}
    if lm:
        out["mamba"] = {
            "norm": ((lm, d), F32, "ones"),
            "in_proj": ((lm, d, m["in_dim"]), BF16, "normal"),
            "conv_w": ((lm, model["ssm_conv"], m["conv_dim"]), BF16, "normal"),
            "conv_b": ((lm, m["conv_dim"]), BF16, "normal"),
            "A_log": ((lm, m["heads"]), F32, "zeros"),
            "D": ((lm, m["heads"]), F32, "ones"),
            "dt_bias": ((lm, m["heads"]), F32, "zeros"),
            "gate_norm": ((lm, m["d_inner"]), F32, "ones"),
            "out_proj": ((lm, m["d_inner"], d), BF16, "normal"),
        }
    if la:
        out["attn"] = {"norm": ((la, d), F32, "ones"),
                       "wq": ((la, d, q), BF16, "normal"),
                       "wk": ((la, d, kv), BF16, "normal"),
                       "wv": ((la, d, kv), BF16, "normal"),
                       "wo": ((la, q, d), BF16, "normal")}
    if le:
        out["moe"] = {
            "norm": ((le, d), F32, "ones"),
            "router": ((le, d, model["n_experts"]), BF16, "normal"),
            "wg": ((le, e, d, f), BF16, "normal"),
            "w1": ((le, e, d, f), BF16, "normal"),
            "w2": ((le, e, f, d), BF16, "normal"),
            "shared": {"wg": ((le, d, fs), BF16, "normal"),
                       "w1": ((le, d, fs), BF16, "normal"),
                       "w2": ((le, fs, d), BF16, "normal")},
        }
    return out


def mamba2(x: jax.Array, lp: Dict, model: Dict, mm: Callable) -> jax.Array:
    """One Mamba2 mixer over the whole sequence. x: (S, d) -> (S, d)."""
    m = dims(model)
    s, di, n, h = x.shape[0], m["d_inner"], m["state"], m["heads"]
    zxbcdt = mm(x, lp["in_proj"])
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:di + m["conv_dim"]]
    dt = zxbcdt[:, di + m["conv_dim"]:]
    w = lp["conv_w"].astype(jnp.float32)                      # (K, conv)
    k = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc])
    xbc = jax.nn.silu(sum(w[i] * xp[i:i + s] for i in range(k))
                      + lp["conv_b"].astype(jnp.float32))
    xs, b, c = xbc[:, :di], xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])                  # (S, H)
    cum = jnp.cumsum(dt * -jnp.exp(lp["A_log"]), axis=0)      # (S, H)
    causal = jnp.tril(jnp.ones((s, s), bool))[:, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(
        causal, cum[:, None, :] - cum[None, :, :], 0.0)), 0.0)  # (t, s, H)
    cb = jnp.matmul(c, b.T, precision=HIGHEST)                # (t, s)
    xh = xs.reshape(s, h, model["ssm_d_head"])
    y = jnp.einsum("tsh,shp->thp", cb[:, :, None] * decay * dt[None],
                   xh, precision=HIGHEST)
    y = (y + lp["D"][None, :, None] * xh).reshape(s, di)
    y = rmsnorm(y * jax.nn.silu(z), lp["gate_norm"], model["rms_norm_eps"])
    return mm(y, lp["out_proj"])


def attention(x: jax.Array, p: Dict, model: Dict, mm: Callable) -> jax.Array:
    """Causal grouped-query attention, no position encoding, scores times
    ``attention_multiplier``.  x: (S, d) -> (S, d)."""
    s = x.shape[0]
    h, kv, dh = model["n_heads"], model["n_kv_heads"], model["d_head"]
    q = mm(x, p["wq"]).reshape(s, h, dh)
    k = jnp.repeat(mm(x, p["wk"]).reshape(s, kv, dh), h // kv, axis=1)
    v = jnp.repeat(mm(x, p["wv"]).reshape(s, kv, dh), h // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        * model["attention_multiplier"]
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST)
    return mm(o.reshape(s, h * dh), p["wo"])


def swiglu(x: jax.Array, wg, w1, w2, mm: Callable) -> jax.Array:
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, w1), w2)


def experts(x: jax.Array, p: Dict, model: Dict, mm: Callable) -> jax.Array:
    """The held experts' part of the expert block, and the shared expert."""
    top, idx = jax.lax.top_k(mm(x, p["router"]), model["top_k"])
    gate = jax.nn.softmax(top, axis=-1)                       # (S, k)
    sh = p["shared"]
    out = swiglu(x, sh["wg"], sh["w1"], sh["w2"], mm)
    for e in range(model["experts_held"]):
        g = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)  # (S,)
        out = out + g[:, None] * swiglu(x, p["wg"][e], p["w1"][e],
                                        p["w2"][e], mm)
    return out


BLOCKS = {"mamba2": mamba2, "attention": attention, "experts": experts}


def hidden(params: Dict, model: Dict, tokens: jax.Array,
           mm: Callable) -> jax.Array:
    """Final-norm hidden states (S, d) of ``tokens`` (S,) over
    ``logits_scaling``, one period of ``layer_types`` at a time."""
    eps, r = model["rms_norm_eps"], model["residual_multiplier"]
    layers = _layers(model)
    period = next(p for p in range(1, len(layers) + 1)
                  if len(layers) % p == 0
                  and all(layers[i] == layers[i % p]
                          for i in range(len(layers))))
    groups = len(layers) // period
    stacks = {k: jax.tree.map(
        lambda a: a.reshape((groups, a.shape[0] // groups) + a.shape[1:]),
        params[v]) for k, v in STACK.items() if v in params}
    x = params["embedding"][tokens].astype(jnp.float32) \
        * model["embedding_multiplier"]

    def group(x, gp):
        seen = {k: 0 for k in STACK}
        for kinds in layers[:period]:
            for kind in kinds:
                lp = jax.tree.map(lambda a: a[seen[kind]], gp[kind])
                seen[kind] += 1
                x = x + r * BLOCKS[kind](rmsnorm(x, lp["norm"], eps), lp,
                                         model, mm)
        return x, None

    x, _ = jax.lax.scan(group, x, stacks)
    return rmsnorm(x, params["final_norm"], eps) / model["logits_scaling"]
