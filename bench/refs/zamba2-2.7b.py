"""Plain float32 reference of zamba2-2.7b as served (arXiv:2411.15242;
sizes from the configuration file).

A stack of Mamba2 blocks (RMSNorm, in-projection to z | x B C | dt, a
causal depthwise convolution of width ``ssm_conv`` with SiLU over x B C, the
selective state-space scan, a gate by SiLU(z), RMSNorm, out-projection),
with one shared transformer block applied after every
``shared_attn_period`` Mamba2 blocks: its input is the hidden state
concatenated with the token embedding, projected back to ``d_model``,
followed by attention with RoPE and an MLP, each with a residual, and its
output added to the hidden state.

The scan is written in its quadratic form, from its definition:
``y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<k<=t} dt_k A) dt_s x_s + D x_t``.

Departures from the published model, which the program makes too: one
shared block (the release alternates two, with LoRA adapters on its MLP),
the shared block's attention runs at ``d_model`` after the down-projection
(the release attends over the concatenated width), a SwiGLU MLP (the release
uses GELU), one group of B and C, no convolution bias, and a norm epsilon
of 1e-6."""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp

from bench.cost import padded_vocab
from bench.refs.common import HIGHEST, attention, mlp, rmsnorm

BF16, F32 = "bfloat16", "float32"


def dims(model: Dict) -> Dict[str, int]:
    d_inner = model["ssm_expand"] * model["d_model"]
    n = model["ssm_state"]
    heads = d_inner // model["ssm_d_head"]
    return {"d_inner": d_inner, "heads": heads, "state": n,
            "conv_dim": d_inner + 2 * n,
            "in_dim": 2 * d_inner + 2 * n + heads}


def layout(model: Dict) -> Dict:
    L, d, ff = model["n_layers"], model["d_model"], model["d_ff"]
    m = dims(model)
    q = model["n_heads"] * model["d_head"]
    kv = model["n_kv_heads"] * model["d_head"]
    mlp_p = {"w1": ((d, ff), BF16, "normal"), "w2": ((ff, d), BF16, "normal")}
    if model["act"] == "swiglu":
        mlp_p["wg"] = ((d, ff), BF16, "normal")
    return {
        "embedding": ((padded_vocab(model), d), BF16, "embed"),
        "mamba": {
            "norm": ((L, d), F32, "ones"),
            "in_proj": ((L, d, m["in_dim"]), BF16, "normal"),
            "conv_w": ((L, model["ssm_conv"], m["conv_dim"]), BF16, "normal"),
            "A_log": ((L, m["heads"]), F32, "zeros"),
            "D": ((L, m["heads"]), F32, "ones"),
            "dt_bias": ((L, m["heads"]), F32, "zeros"),
            "gate_norm": ((L, m["d_inner"]), F32, "ones"),
            "out_proj": ((L, m["d_inner"], d), BF16, "normal"),
        },
        "shared": {
            "norm_attn": ((d,), F32, "ones"),
            "norm_mlp": ((d,), F32, "ones"),
            "attn": {"wq": ((d, q), BF16, "normal"),
                     "wk": ((d, kv), BF16, "normal"),
                     "wv": ((d, kv), BF16, "normal"),
                     "wo": ((q, d), BF16, "normal")},
            "mlp": mlp_p,
            "down": ((2 * d, d), BF16, "normal"),
        },
        "final_norm": ((d,), F32, "ones"),
    }


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
    xbc = jax.nn.silu(sum(w[i] * xp[i:i + s] for i in range(k)))
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


def hidden(params: Dict, model: Dict, tokens: jax.Array,
           mm: Callable) -> jax.Array:
    """Final-norm hidden states (S, d) of ``tokens`` (S,), group by group."""
    eps, period = model["rms_norm_eps"], model["shared_attn_period"]
    groups = model["n_layers"] // period
    x = params["embedding"][tokens].astype(jnp.float32)
    e0 = x
    sp = params["shared"]
    stack = jax.tree.map(lambda a: a.reshape((groups, period) + a.shape[1:]),
                         params["mamba"])

    def group(x, gp):
        def inner(x, lp):
            return x + mamba2(rmsnorm(x, lp["norm"], eps), lp, model, mm), None

        x, _ = jax.lax.scan(inner, x, gp)
        h = mm(jnp.concatenate([x, e0], -1), sp["down"])
        h = h + attention(rmsnorm(h, sp["norm_attn"], eps), sp["attn"],
                          model, mm)
        h = h + mlp(rmsnorm(h, sp["norm_mlp"], eps), sp["mlp"], model, mm)
        return x + h, None

    x, _ = jax.lax.scan(group, x, stack)
    return rmsnorm(x, params["final_norm"], eps)
