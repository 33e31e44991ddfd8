"""Plain float32 reference of phi4-mini-3.8b as served: a decoder-only
stack of pre-norm blocks (RMSNorm, grouped-query attention with RoPE,
SwiGLU MLP), a final RMSNorm and logits against the tied embedding
(arXiv:2412.08905; sizes from the configuration file).

Departures from the published model, which the program makes too: RoPE
turns the whole head (the release rotates 3/4 of it, with LongRoPE
scaling), and the vocabulary is padded to a multiple of 256 with rows that
take part in the logits."""

from __future__ import annotations

from typing import Callable, Dict

import jax

from bench.cost import padded_vocab
from bench.refs.common import attention, mlp, rmsnorm

BF16, F32 = "bfloat16", "float32"


def layout(model: Dict) -> Dict:
    L, d, ff = model["n_layers"], model["d_model"], model["d_ff"]
    q = model["n_heads"] * model["d_head"]
    kv = model["n_kv_heads"] * model["d_head"]
    mlp_p = {"w1": ((L, d, ff), BF16, "normal"),
             "w2": ((L, ff, d), BF16, "normal")}
    if model["act"] == "swiglu":
        mlp_p["wg"] = ((L, d, ff), BF16, "normal")
    return {
        "embedding": ((padded_vocab(model), d), BF16, "embed"),
        "blocks": {
            "norm_attn": ((L, d), F32, "ones"),
            "norm_mlp": ((L, d), F32, "ones"),
            "attn": {"wq": ((L, d, q), BF16, "normal"),
                     "wk": ((L, d, kv), BF16, "normal"),
                     "wv": ((L, d, kv), BF16, "normal"),
                     "wo": ((L, q, d), BF16, "normal")},
            "mlp": mlp_p,
        },
        "final_norm": ((d,), F32, "ones"),
    }


def hidden(params: Dict, model: Dict, tokens: jax.Array,
           mm: Callable) -> jax.Array:
    """Final-norm hidden states (S, d) of ``tokens`` (S,), layer by layer."""
    eps = model["rms_norm_eps"]
    x = params["embedding"][tokens].astype("float32")

    def block(x, lp):
        x = x + attention(rmsnorm(x, lp["norm_attn"], eps), lp["attn"],
                          model, mm)
        x = x + mlp(rmsnorm(x, lp["norm_mlp"], eps), lp["mlp"], model, mm)
        return x, None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    return rmsnorm(x, params["final_norm"], eps)
