"""Useful work of the served model, from the configuration file's shapes.

FLOPs count 2 per multiply-accumulate of the matrix products a token needs
(projections, MLP, attention scores and values over the positions it
attends to, the SSM update and read-out, the unembedding where logits are
produced).  Bytes count what one batched decode step has to move at least:
every weight once, the K/V of the positions each active slot holds, the new
K/V row it writes, and the recurrent state it reads and writes.  Nothing the
program recomputes, pads or reads in excess is counted.

``model`` is the ``"model"`` object of a ``bench/configs/*.json`` file.
"""

from __future__ import annotations

from typing import Dict, Iterable

BF16, F32 = 2, 4


def padded_vocab(model: Dict) -> int:
    m = model["vocab_pad_multiple"]
    return (model["vocab_size"] + m - 1) // m * m


def _mlp_mats(model: Dict) -> int:
    return 3 if model.get("act", "swiglu") == "swiglu" else 2


def _attn_proj_flops(model: Dict) -> int:
    d, q = model["d_model"], model["n_heads"] * model["d_head"]
    kv = model["n_kv_heads"] * model["d_head"]
    return 2 * d * (q + 2 * kv) + 2 * q * d


def _mlp_flops(model: Dict) -> int:
    return 2 * model["d_model"] * model["d_ff"] * _mlp_mats(model)


def mamba_dims(model: Dict) -> Dict[str, int]:
    d_inner = model["ssm_expand"] * model["d_model"]
    n = model["ssm_state"]
    heads = d_inner // model["ssm_d_head"]
    return {"d_inner": d_inner, "heads": heads, "state": n,
            "head_dim": model["ssm_d_head"],
            "conv_dim": d_inner + 2 * n,
            "in_dim": 2 * d_inner + 2 * n + heads}


def _mamba_flops(model: Dict) -> int:
    m = mamba_dims(model)
    proj = 2 * model["d_model"] * m["in_dim"] + 2 * m["d_inner"] * model["d_model"]
    conv = 2 * model["ssm_conv"] * m["conv_dim"]
    ssm = 4 * m["heads"] * m["state"] * m["head_dim"]   # update + read-out
    return proj + conv + ssm


def n_attention_layers(model: Dict) -> int:
    if model["family"] == "hybrid":
        return model["n_layers"] // model["shared_attn_period"]
    return model["n_layers"]


def token_flops(model: Dict) -> int:
    """Matrix-product FLOPs of one token through every layer, without the
    attention over earlier positions and without the unembedding."""
    if model["family"] == "dense":
        return model["n_layers"] * (_attn_proj_flops(model) + _mlp_flops(model))
    if model["family"] == "hybrid":
        d = model["d_model"]
        shared = 2 * (2 * d) * d + _attn_proj_flops(model) + _mlp_flops(model)
        return (model["n_layers"] * _mamba_flops(model)
                + n_attention_layers(model) * shared)
    raise ValueError(f"unknown family {model['family']!r}")


def attention_flops(model: Dict, keys: int) -> int:
    """Scores and values of one query over ``keys`` positions, all layers."""
    return n_attention_layers(model) * 4 * keys * model["n_heads"] * model["d_head"]


def head_flops(model: Dict) -> int:
    """Logits of one position over the (padded) vocabulary."""
    return 2 * model["d_model"] * padded_vocab(model)


def prefill_flops(model: Dict, prompt_len: int) -> int:
    """Absorbing a prompt of ``prompt_len`` tokens: every token through every
    layer, causal attention (token ``p`` attends ``p + 1`` positions), and
    logits at the last position only.  The same useful work whether the
    program absorbs it in one prefill or one decode step per token."""
    pairs = prompt_len * (prompt_len + 1) // 2
    return (prompt_len * token_flops(model)
            + attention_flops(model, pairs) + head_flops(model))


def decode_flops(model: Dict, keys: Iterable[int]) -> int:
    """One batched decode step: one token for each active slot, which
    attends ``k`` positions (its cache plus the token itself)."""
    keys = list(keys)
    return (len(keys) * (token_flops(model) + head_flops(model))
            + sum(attention_flops(model, k) for k in keys))


def weight_bytes(model: Dict) -> int:
    """Bytes of every parameter in the dtype the program serves it in."""
    return param_counts(model)["bf16"] * BF16 + param_counts(model)["f32"] * F32


def param_counts(model: Dict) -> Dict[str, int]:
    d, v = model["d_model"], padded_vocab(model)
    q = model["n_heads"] * model["d_head"]
    kv = model["n_kv_heads"] * model["d_head"]
    attn = d * q + 2 * d * kv + q * d
    mlp = _mlp_mats(model) * d * model["d_ff"]
    if model["family"] == "dense":
        L = model["n_layers"]
        return {"bf16": v * d + L * (attn + mlp), "f32": (2 * L + 1) * d}
    if model["family"] == "hybrid":
        m, L = mamba_dims(model), model["n_layers"]
        mamba_bf16 = (d * m["in_dim"] + model["ssm_conv"] * m["conv_dim"]
                      + m["d_inner"] * d)
        mamba_f32 = d + 3 * m["heads"] + m["d_inner"]
        return {"bf16": v * d + L * mamba_bf16 + attn + mlp + 2 * d * d,
                "f32": L * mamba_f32 + 3 * d}
    raise ValueError(f"unknown family {model['family']!r}")


def kv_bytes_per_position(model: Dict) -> int:
    """K and V of one position in every attention layer (bf16)."""
    return (n_attention_layers(model) * 2 * model["n_kv_heads"]
            * model["d_head"] * BF16)


def state_bytes_per_slot(model: Dict) -> int:
    """Recurrent state of one slot: SSM state (f32) and conv window (bf16)."""
    if model["family"] != "hybrid":
        return 0
    m = mamba_dims(model)
    return model["n_layers"] * (
        m["heads"] * m["state"] * m["head_dim"] * F32
        + (model["ssm_conv"] - 1) * m["conv_dim"] * BF16)


def decode_bytes(model: Dict, keys: Iterable[int]) -> int:
    """Least HBM traffic of one batched decode step: the weights once; per
    active slot the K/V of the ``k`` positions it attends (the new row is
    written, then read) and its recurrent state read and written."""
    keys = list(keys)
    kv = kv_bytes_per_position(model)
    return (weight_bytes(model) + sum(k * kv for k in keys) + len(keys) * kv
            + 2 * len(keys) * state_bytes_per_slot(model))
