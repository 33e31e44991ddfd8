"""Useful work of the served model, from the configuration file's shapes.

FLOPs count 2 per multiply-accumulate of the matrix products a token needs
(projections, MLP, experts, attention scores and values over the positions
it attends to, the SSM update and read-out, the unembedding where logits
are produced).  Bytes count what one batched decode step has to move at
least: every weight once (of a layer's routed experts, those that a token
hit), the K/V of the positions each active slot holds, the new K/V row it
writes, and the recurrent state it reads and writes.  Nothing the program
recomputes, pads or reads in excess is counted.

``model`` is the ``"model"`` object of a ``bench/configs/*.json`` file.  Its
layers are blocks of the kinds in :data:`KINDS`.  ``model["layer_types"]``
lists them, one entry per layer, each a kind or a list of kinds (say
``["mamba2", "experts"]``); without it, ``family`` names a pattern:
``dense`` is ``attention`` then ``mlp`` in every layer, ``hybrid`` is a
``mamba2`` block in every layer with one ``shared_attention`` block after
every ``shared_attn_period`` of them.  Every model also has a tied
embedding of the padded vocabulary and a final norm.
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Callable, Dict, Iterable, List, Tuple

from bench import BenchError

BF16, F32 = 2, 4


def padded_vocab(model: Dict) -> int:
    m = model["vocab_pad_multiple"]
    return (model["vocab_size"] + m - 1) // m * m


def _mlp_mats(model: Dict) -> int:
    return 3 if model.get("act", "swiglu") == "swiglu" else 2


def mamba_dims(model: Dict) -> Dict[str, int]:
    d_inner = model["ssm_expand"] * model["d_model"]
    n = model["ssm_state"]
    heads = d_inner // model["ssm_d_head"]
    return {"d_inner": d_inner, "heads": heads, "state": n,
            "head_dim": model["ssm_d_head"],
            "conv_dim": d_inner + 2 * n,
            "in_dim": 2 * d_inner + 2 * n + heads}


# ---- one block of each kind ----------------------------------------------------

def _attn_mats(model: Dict) -> int:
    d, q = model["d_model"], model["n_heads"] * model["d_head"]
    kv = model["n_kv_heads"] * model["d_head"]
    return d * q + 2 * d * kv + q * d


def _mlp_mat(model: Dict) -> int:
    return _mlp_mats(model) * model["d_model"] * model["d_ff"]


def _mamba_mats(model: Dict) -> int:
    m, d = mamba_dims(model), model["d_model"]
    return d * m["in_dim"] + m["d_inner"] * d


def _mamba_params(model: Dict) -> Tuple[int, int]:
    m, d = mamba_dims(model), model["d_model"]
    conv = (model["ssm_conv"] + bool(model.get("ssm_conv_bias"))) * m["conv_dim"]
    return (_mamba_mats(model) + conv, d + 3 * m["heads"] + m["d_inner"])


def _mamba_flops(model: Dict) -> int:
    m = mamba_dims(model)
    conv = 2 * model["ssm_conv"] * m["conv_dim"]
    ssm = 4 * m["heads"] * m["state"] * m["head_dim"]   # update + read-out
    return 2 * _mamba_mats(model) + conv + ssm


def _mamba_state(model: Dict) -> int:
    m = mamba_dims(model)
    return (m["heads"] * m["state"] * m["head_dim"] * F32
            + (model["ssm_conv"] - 1) * m["conv_dim"] * BF16)


def _kv_row(model: Dict) -> int:
    return 2 * model["n_kv_heads"] * model["d_head"] * BF16


def _shared_mats(model: Dict) -> int:
    d = model["d_model"]
    return 2 * d * d + _attn_mats(model) + _mlp_mat(model)


def _expert_mats(model: Dict) -> int:
    return _mlp_mats(model) * model["d_model"] * model["d_expert"]


def _experts_params(model: Dict) -> Tuple[int, int]:
    """The router over every published expert and the shared expert, if
    any; the routed experts held here are counted apart."""
    d = model["d_model"]
    shared = _mlp_mats(model) * d * model.get("d_shared_expert", 0)
    return d * model["n_experts"] + shared, d


def _zero(model: Dict) -> int:
    return 0


@dataclasses.dataclass(frozen=True)
class Kind:
    """What one block of a kind costs.  ``params``: (bf16, f32) parameters
    every use reads; ``flops``: FLOPs per token through it, but attention
    over earlier positions (``attends``: 4 x heads x head size a position)
    and routed experts; ``kv``: K/V bytes per position; ``state``: state
    bytes per slot; ``expert``: bf16 parameters of one routed expert, each
    a multiply-accumulate for every (token, expert) pair routed to it;
    ``shared``: weights counted once however often the block is used."""
    params: Callable[[Dict], Tuple[int, int]]
    flops: Callable[[Dict], int]
    kv: Callable[[Dict], int] = _zero
    state: Callable[[Dict], int] = _zero
    expert: Callable[[Dict], int] = _zero
    attends: bool = False
    shared: bool = False


KINDS: Dict[str, Kind] = {
    "attention": Kind(
        params=lambda m: (_attn_mats(m), m["d_model"]),
        flops=lambda m: 2 * _attn_mats(m), kv=_kv_row, attends=True),
    # zamba2's block: [x, embedding] projected down, attention, MLP
    "shared_attention": Kind(
        params=lambda m: (_shared_mats(m), 2 * m["d_model"]),
        flops=lambda m: 2 * _shared_mats(m), kv=_kv_row, attends=True,
        shared=True),
    "mamba2": Kind(params=_mamba_params, flops=_mamba_flops,
                   state=_mamba_state),
    "mlp": Kind(params=lambda m: (_mlp_mat(m), m["d_model"]),
                flops=lambda m: 2 * _mlp_mat(m)),
    "experts": Kind(params=_experts_params,
                    flops=lambda m: 2 * _experts_params(m)[0],
                    expert=_expert_mats),
}


# ---- the model's blocks -------------------------------------------------------

def blocks(model: Dict, where: str = "the configuration") -> List[str]:
    """Every block of the model, in order, by kind; ``where`` names the
    file in the error an unknown kind or pattern raises."""
    if "layer_types" in model:
        layers = model["layer_types"]
    elif model.get("family") == "dense":
        layers = [["attention", "mlp"]] * model["n_layers"]
    elif model.get("family") == "hybrid":
        p = model["shared_attn_period"]
        layers = [["mamba2", "shared_attention"] if i % p == p - 1
                  else "mamba2" for i in range(model["n_layers"])]
    else:
        raise BenchError(f"{where}: no layer_types, and family "
                         f"{model.get('family')!r} names no pattern")
    out = [k for layer in layers
           for k in ([layer] if isinstance(layer, str) else layer)]
    for k in out:
        if k not in KINDS:
            raise BenchError(f"{where}: unknown layer kind {k!r}; known: "
                             f"{sorted(KINDS)}")
    return out


def _uses(model: Dict) -> Dict[str, int]:
    return Counter(blocks(model))


def n_attention_layers(model: Dict) -> int:
    return sum(n for k, n in _uses(model).items() if KINDS[k].attends)


def n_expert_layers(model: Dict) -> int:
    return _uses(model).get("experts", 0)


def experts_held(model: Dict) -> int:
    return model.get("experts_held", model.get("n_experts", 0))


def expected_pairs(model: Dict, tokens: int) -> float:
    """(token, held expert) pairs over every expert layer for ``tokens``
    tokens, routed uniformly: top-k x held / published a token a layer."""
    return (n_expert_layers(model) * tokens * model["top_k"]
            * experts_held(model) / model["n_experts"])


def expected_hit(model: Dict, tokens: int) -> float:
    """Held experts that at least one of ``tokens`` tokens is routed to,
    over every expert layer (uniform routing); for one token, as many as
    :func:`expected_pairs`."""
    miss = (1 - model["top_k"] / model["n_experts"]) ** tokens
    return n_expert_layers(model) * experts_held(model) * (1 - miss)


def _routed(model: Dict, tokens: int) -> float:
    """FLOPs of the routed experts for ``tokens`` tokens, at the expected
    (token, held expert) pairs; 0 without experts."""
    if not n_expert_layers(model):
        return 0
    return expected_pairs(model, tokens) * 2 * KINDS["experts"].expert(model)


# ---- work and bytes --------------------------------------------------------------

def token_flops(model: Dict) -> int:
    """Matrix-product FLOPs of one token through every block, without the
    attention over earlier positions, the routed experts and the
    unembedding."""
    return sum(n * KINDS[k].flops(model) for k, n in _uses(model).items())


def attention_flops(model: Dict, keys: int) -> int:
    """Scores and values of one query over ``keys`` positions, all layers."""
    return n_attention_layers(model) * 4 * keys * model["n_heads"] * model["d_head"]


def head_flops(model: Dict) -> int:
    """Logits of one position over the (padded) vocabulary."""
    return 2 * model["d_model"] * padded_vocab(model)


def prefill_flops(model: Dict, prompt_len: int) -> float:
    """Absorbing a prompt of ``prompt_len`` tokens: every token through every
    layer, causal attention (token ``p`` attends ``p + 1`` positions), and
    logits at the last position only.  The same useful work whether the
    program absorbs it in one prefill or one decode step per token."""
    causal = prompt_len * (prompt_len + 1) // 2
    return (prompt_len * token_flops(model) + attention_flops(model, causal)
            + head_flops(model) + _routed(model, prompt_len))


def decode_flops(model: Dict, keys: Iterable[int]) -> float:
    """One batched decode step: one token for each active slot, which
    attends ``k`` positions (its cache plus the token itself)."""
    keys = list(keys)
    return (len(keys) * (token_flops(model) + head_flops(model))
            + sum(attention_flops(model, k) for k in keys)
            + _routed(model, len(keys)))


def param_counts(model: Dict) -> Dict[str, int]:
    """Parameters by the dtype the program serves them in: the embedding,
    the final norm, each block's (a shared block's once) and every routed
    expert held here."""
    d = model["d_model"]
    bf16, f32 = padded_vocab(model) * d, d
    for k, n in _uses(model).items():
        kind = KINDS[k]
        times = 1 if kind.shared else n
        b, f = kind.params(model)
        bf16 += times * (b + experts_held(model) * kind.expert(model))
        f32 += times * f
    return {"bf16": bf16, "f32": f32}


def weight_bytes(model: Dict) -> int:
    """Bytes of every parameter in the dtype the program serves it in."""
    counts = param_counts(model)
    return counts["bf16"] * BF16 + counts["f32"] * F32


def kv_bytes_per_position(model: Dict) -> int:
    """K and V of one position in every attention layer (bf16)."""
    return sum(n * KINDS[k].kv(model) for k, n in _uses(model).items())


def state_bytes_per_slot(model: Dict) -> int:
    """Recurrent state of one slot: SSM state (f32) and conv window (bf16)."""
    return sum(n * KINDS[k].state(model) for k, n in _uses(model).items())


def decode_bytes(model: Dict, keys: Iterable[int]) -> float:
    """Least HBM traffic of one batched decode step: the weights once, of
    the routed experts only the expected hit ones (over every expert
    layer); per active slot the K/V of the ``k``
    positions it attends (the new row is written, then read) and its
    recurrent state read and written."""
    keys = list(keys)
    kv = kv_bytes_per_position(model)
    weights = weight_bytes(model)
    if n_expert_layers(model):
        hit = expected_hit(model, len(keys))
        held = n_expert_layers(model) * experts_held(model)
        weights -= (held - hit) * KINDS["experts"].expert(model) * BF16
    return (weights + sum(k * kv for k in keys) + len(keys) * kv
            + 2 * len(keys) * state_bytes_per_slot(model))
