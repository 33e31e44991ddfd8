"""State-space / linear-recurrence architectures:

* Mamba2 (SSD, chunked-parallel training form + recurrent decode) — the
  zamba2-2.7b building block [arXiv:2405.21060 / 2411.15242];
* RWKV6 "Finch" time-mix with data-dependent decay + channel-mix
  [arXiv:2404.05892];
* Zamba2 hybrid: stacked Mamba2 blocks with one *shared* attention+MLP block
  applied every ``shared_attn_period`` layers;
* per-layer hybrids (granite-4.0-h): each layer is the kinds its
  ``layer_types`` entry lists (a Mamba2 or attention mixer, then an expert
  block), each a pre-norm residual branch scaled by ``residual_multiplier``.

Training uses chunked matmul forms (MXU-friendly — these are also the Pallas
kernel targets in repro.kernels); decode uses O(1) recurrent state updates.
"""

from __future__ import annotations

import collections
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..backend import current_backend
from ..backend import matmul as bmm
from ..configs.base import LAYER_KINDS, ModelConfig
from .layers import (KVCacheSpec, attention, attention_param_specs, chunked_softmax_xent,
                     decode_attention, embed, embed_param_specs, held_experts,
                     held_experts_param_specs, layer_at, logits_last, mlp, mlp_param_specs,
                     rmsnorm, rmsnorm_spec, route_counts, scan_layers)
from .shardlib import ParamSpec, shard

Params = Dict[str, Any]

EXP_CLAMP = 30.0


def _remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat == "full" else fn


# ===========================================================================
# Mamba2 (SSD)
# ===========================================================================


def mamba2_dims(cfg: ModelConfig) -> Dict[str, int]:
    d_inner = 2 * cfg.d_model
    n_heads = d_inner // cfg.ssm_d_head
    conv_dim = d_inner + 2 * cfg.ssm_state          # x, B, C share the conv
    in_dim = 2 * d_inner + 2 * cfg.ssm_state + n_heads
    return dict(d_inner=d_inner, n_heads=n_heads, conv_dim=conv_dim,
                in_dim=in_dim, d_state=cfg.ssm_state, p=cfg.ssm_d_head)


def mamba2_param_specs(cfg: ModelConfig, layers: int) -> Params:
    dims = mamba2_dims(cfg)
    L, d = layers, cfg.d_model
    bf = jnp.bfloat16
    bias = ({"conv_b": ParamSpec((L, dims["conv_dim"]), bf, ("layers", "tp"))}
            if cfg.ssm_conv_bias else {})
    return {
        **bias,
        "norm": ParamSpec((L, d), jnp.float32, ("layers", None), init="ones"),
        "in_proj": ParamSpec((L, d, dims["in_dim"]), bf,
                             ("layers", "fsdp", "tp")),
        "conv_w": ParamSpec((L, 4, dims["conv_dim"]), bf,
                            ("layers", None, "tp")),
        "A_log": ParamSpec((L, dims["n_heads"]), jnp.float32,
                           ("layers", None), init="zeros"),
        "D": ParamSpec((L, dims["n_heads"]), jnp.float32,
                       ("layers", None), init="ones"),
        "dt_bias": ParamSpec((L, dims["n_heads"]), jnp.float32,
                             ("layers", None), init="zeros"),
        "gate_norm": ParamSpec((L, dims["d_inner"]), jnp.float32,
                               ("layers", None), init="ones"),
        "out_proj": ParamSpec((L, dims["d_inner"], d), bf,
                              ("layers", "tp", "fsdp")),
    }


def _causal_conv(x: jax.Array, w: jax.Array,
                 state: Optional[jax.Array] = None,
                 bias: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal conv, kernel 4. x: (b, s, c), w: (4, c), bias: (c,)."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i][None, None] for i in range(k))
    if bias is not None:
        out = out + bias
    return jax.nn.silu(out.astype(jnp.float32)).astype(x.dtype)


def _split_zxbcdt(zxbcdt: jax.Array, dims: Dict[str, int]):
    z, xbc, dt = jnp.split(
        zxbcdt, [dims["d_inner"], dims["d_inner"] + dims["conv_dim"]], axis=-1)
    return z, xbc, dt


def mamba2_forward(x: jax.Array, lp: Params, cfg: ModelConfig,
                   ssm_state: Optional[jax.Array] = None,
                   conv_state: Optional[jax.Array] = None,
                   return_state: bool = False):
    """Chunked SSD forward. x: (b, s, d) -> (b, s, d) [+ final states].

    Chunk math (per head h, state size N, head dim P):
      da_t = dt_t * -exp(A_log_h); cum_t = cumsum(da) within chunk;
      intra: Y[t] += sum_{s<=t} (C_t . B_s) * exp(cum_t - cum_s) * dt_s x_s
      chunk state: S_c = sum_s exp(cum_last - cum_s) dt_s (B_s (x) x_s)
      carry: R_{c+1} = R_c * exp(cum_last) + S_c ; Y[t] += (C_t . R_c) exp(cum_t)
    """
    dims = mamba2_dims(cfg)
    b, s, _ = x.shape
    zxbcdt = bmm(x, lp["in_proj"])
    z, xbc, dt = _split_zxbcdt(zxbcdt, dims)
    xbc = _causal_conv(xbc, lp["conv_w"], conv_state, lp.get("conv_b"))
    xs, B, C = jnp.split(xbc, [dims["d_inner"],
                               dims["d_inner"] + dims["d_state"]], axis=-1)
    h, p, n = dims["n_heads"], dims["p"], dims["d_state"]
    xh = xs.reshape(b, s, h, p).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])   # (b, s, h)
    a = -jnp.exp(lp["A_log"])                                      # (h,)
    da = dt * a                                                    # (b, s, h)

    ch = min(cfg.ssm_chunk, s)
    if s % ch:
        ch = s
    nc = s // ch
    Bf = B.astype(jnp.float32).reshape(b, nc, ch, n)
    Cf = C.astype(jnp.float32).reshape(b, nc, ch, n)
    dac = da.reshape(b, nc, ch, h)
    xc = (xh * dt[..., None]).reshape(b, nc, ch, h, p)
    cum = jnp.cumsum(dac, axis=2)                                  # (b,nc,ch,h)

    scores = jnp.einsum("bctn,bcsn->bcts", Cf, Bf)                 # (b,nc,t,s)
    decay = jnp.exp(jnp.clip(cum[:, :, :, None] - cum[:, :, None, :],
                             -EXP_CLAMP, EXP_CLAMP))               # (b,nc,t,s,h)
    mask = jnp.tril(jnp.ones((ch, ch), bool))
    w = jnp.where(mask[None, None, :, :, None],
                  scores[..., None] * decay, 0.0)
    y_intra = jnp.einsum("bctsh,bcshp->bcthp", w, xc)

    # per-chunk boundary states
    tail = jnp.exp(jnp.clip(cum[:, :, -1:, :] - cum, -EXP_CLAMP, EXP_CLAMP))
    S_c = jnp.einsum("bcsn,bcsh,bcshp->bchnp", Bf, tail, xc)       # (b,nc,h,n,p)
    chunk_decay = jnp.exp(jnp.clip(cum[:, :, -1, :], -EXP_CLAMP, 0.0))

    R0 = (jnp.zeros((b, h, n, p), jnp.float32) if ssm_state is None
          else ssm_state.astype(jnp.float32))

    def carry_fn(R, inp):
        S, dec = inp
        out = R
        R = R * dec[:, :, None, None] + S
        return R, out

    S_t = jnp.moveaxis(S_c, 1, 0)
    dec_t = jnp.moveaxis(chunk_decay, 1, 0)
    R_final, R_before = jax.lax.scan(carry_fn, R0, (S_t, dec_t))
    R_before = jnp.moveaxis(R_before, 0, 1)                        # (b,nc,h,n,p)

    y_inter = jnp.einsum("bctn,bchnp->bcthp", Cf, R_before)
    y_inter = y_inter * jnp.exp(jnp.clip(cum, -EXP_CLAMP, 0.0))[..., None]
    y = (y_intra + y_inter).reshape(b, s, h, p)
    y = y + lp["D"][None, None, :, None] * xh
    y = y.reshape(b, s, dims["d_inner"])

    gated = y * jax.nn.silu(z.astype(jnp.float32))
    gated = rmsnorm(gated.astype(jnp.bfloat16), lp["gate_norm"],
                    cfg.rms_norm_eps)
    out = bmm(gated, lp["out_proj"])
    if return_state:
        conv_out = jnp.concatenate(
            [conv_state.astype(xbc.dtype) if conv_state is not None else
             jnp.zeros((b, 3, dims["conv_dim"]), xbc.dtype),
             # pre-activation conv input tail: slice the projection already
             # computed above (a second bmm would re-run the GEMM on the
             # host backend and double-count its MACs/energy)
             zxbcdt[:, :, dims["d_inner"]:dims["d_inner"] +
                    dims["conv_dim"]]], axis=1)[:, -3:]
        return out, R_final, conv_out
    return out


def mamba2_step(x: jax.Array, lp: Params, cfg: ModelConfig,
                ssm_state: jax.Array, conv_state: jax.Array):
    """Single-token recurrence. x: (b, 1, d); ssm_state: (b, h, n, p);
    conv_state: (b, 3, conv_dim) raw pre-conv inputs."""
    dims = mamba2_dims(cfg)
    b = x.shape[0]
    zxbcdt = bmm(x, lp["in_proj"])
    z, xbc_new, dt = _split_zxbcdt(zxbcdt, dims)
    window = jnp.concatenate([conv_state.astype(xbc_new.dtype), xbc_new], axis=1)
    conv_w = lp["conv_w"]
    xbc = sum(window[:, i] * conv_w[i][None] for i in range(4))
    if "conv_b" in lp:
        xbc = xbc + lp["conv_b"][None]
    xbc = jax.nn.silu(xbc.astype(jnp.float32)).astype(x.dtype)     # (b, conv)
    xs, B, C = jnp.split(xbc, [dims["d_inner"],
                               dims["d_inner"] + dims["d_state"]], axis=-1)
    h, p, n = dims["n_heads"], dims["p"], dims["d_state"]
    xh = xs.reshape(b, h, p).astype(jnp.float32)
    dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + lp["dt_bias"])  # (b,h)
    da = jnp.exp(jnp.clip(dt1 * -jnp.exp(lp["A_log"]), -EXP_CLAMP, 0.0))
    Bf = B.astype(jnp.float32)                                     # (b, n)
    Cf = C.astype(jnp.float32)
    new_state = (ssm_state * da[:, :, None, None]
                 + jnp.einsum("bn,bh,bhp->bhnp", Bf, dt1, xh))
    y = jnp.einsum("bn,bhnp->bhp", Cf, new_state) + lp["D"][None, :, None] * xh
    y = y.reshape(b, 1, dims["d_inner"])
    gated = y * jax.nn.silu(z.astype(jnp.float32))
    gated = rmsnorm(gated.astype(jnp.bfloat16), lp["gate_norm"],
                    cfg.rms_norm_eps)
    out = bmm(gated, lp["out_proj"])
    return out, new_state, window[:, -3:]


# ===========================================================================
# RWKV6 (Finch)
# ===========================================================================


def rwkv6_dims(cfg: ModelConfig) -> Dict[str, int]:
    return dict(h=cfg.n_heads, p=cfg.d_head, d=cfg.d_model,
                lora=max(32, cfg.d_model // 64))


def rwkv6_param_specs(cfg: ModelConfig) -> Params:
    dims = rwkv6_dims(cfg)
    L, d, lora = cfg.n_layers, cfg.d_model, dims["lora"]
    bf = jnp.bfloat16
    return {
        "norm_att": ParamSpec((L, d), jnp.float32, ("layers", None), init="ones"),
        "norm_ffn": ParamSpec((L, d), jnp.float32, ("layers", None), init="ones"),
        # time-mix interpolation coefficients for r,k,v,w,g
        "tmix_mu": ParamSpec((L, 5, d), jnp.float32, ("layers", None, None),
                        init="zeros"),
        "wr": ParamSpec((L, d, d), bf, ("layers", "fsdp", "tp")),
        "wk": ParamSpec((L, d, d), bf, ("layers", "fsdp", "tp")),
        "wv": ParamSpec((L, d, d), bf, ("layers", "fsdp", "tp")),
        "wg": ParamSpec((L, d, d), bf, ("layers", "fsdp", "tp")),
        "wo": ParamSpec((L, d, d), bf, ("layers", "tp", "fsdp")),
        # data-dependent decay: w = exp(-exp(base + tanh(x A) B))
        "w_base": ParamSpec((L, d), jnp.float32, ("layers", None), init="zeros"),
        "w_lora_a": ParamSpec((L, d, lora), bf, ("layers", "fsdp", None)),
        "w_lora_b": ParamSpec((L, lora, d), bf, ("layers", None, "tp")),
        "u": ParamSpec((L, dims["h"], dims["p"]), jnp.float32,
                       ("layers", None, None), init="zeros"),
        "ln_x": ParamSpec((L, d), jnp.float32, ("layers", None), init="ones"),
        # channel mix
        "cmix_mu": ParamSpec((L, 2, d), jnp.float32, ("layers", None, None),
                            init="zeros"),
        "ck": ParamSpec((L, d, cfg.d_ff), bf, ("layers", "fsdp", "tp")),
        "cv": ParamSpec((L, cfg.d_ff, d), bf, ("layers", "tp", "fsdp")),
        "cr": ParamSpec((L, d, d), bf, ("layers", "fsdp", "tp")),
    }


def _token_shift(x: jax.Array, prev: Optional[jax.Array] = None) -> jax.Array:
    """(b, s, d) -> previous-token tensor; `prev` seeds position 0 (decode)."""
    first = (jnp.zeros_like(x[:, :1]) if prev is None
             else prev[:, None].astype(x.dtype))
    return jnp.concatenate([first, x[:, :-1]], axis=1)


def wkv6_chunked(r, k, v, w_log, u, state, chunk: int,
                 compute_dtype=jnp.float32):
    """Chunked WKV recurrence (shared by model fwd and kernels/ref).

      y_t = r_t . (S_{t-1} + (u (*) k_t) v_t^T) ; S_t = diag(w_t) S_{t-1} + k_t v_t^T

    r,k,v: (b, s, h, p) f32; w_log: (b, s, h, p) = log decay (<= 0);
    u: (h, p); state: (b, h, p, p).  Returns (y, final_state).
    """
    b, s, h, p = r.shape
    ch = min(chunk, s)
    if s % ch:
        ch = s
    nc = s // ch
    rc = r.reshape(b, nc, ch, h, p).astype(compute_dtype)
    kc = k.reshape(b, nc, ch, h, p).astype(compute_dtype)
    vc = v.reshape(b, nc, ch, h, p).astype(compute_dtype)
    lw = jnp.cumsum(w_log.reshape(b, nc, ch, h, p), axis=2)   # f32 cumsum

    # A[t, s] = sum_p r_t,p k_s,p exp(lw_{t-1,p} - lw_{s,p})  for s < t.
    # Exponents are centred at half the chunk's total decay so exp() stays in
    # f32 range for any chunk length (products telescope to <= 1).
    lw_prev = jnp.concatenate([jnp.zeros_like(lw[:, :, :1]), lw[:, :, :-1]],
                              axis=2)
    m = 0.5 * lw[:, :, -1:]
    rr = rc * jnp.exp(jnp.clip(lw_prev - m, -2 * EXP_CLAMP,
                               2 * EXP_CLAMP)).astype(compute_dtype)
    kk = kc * jnp.exp(jnp.clip(m - lw, -2 * EXP_CLAMP,
                               2 * EXP_CLAMP)).astype(compute_dtype)
    A = jnp.einsum("bcthp,bcshp->bchts", rr, kk).astype(compute_dtype)
    mask = jnp.tril(jnp.ones((ch, ch), bool), k=-1)
    A = jnp.where(mask[None, None, None], A, 0.0)
    diag = jnp.einsum("bcthp,hp,bcthp->bcth", rc, u, kc)
    y = jnp.einsum("bchts,bcshp->bcthp", A, vc)
    y = y + diag[..., None] * vc

    # inter-chunk: y += (r_t (*) exp(lw_{t-1})) . S_in ; state updates
    tail = jnp.exp(jnp.clip(lw[:, :, -1:] - lw, -EXP_CLAMP, EXP_CLAMP))
    k_tail = kc * tail                                          # decay to end
    S_c = jnp.einsum("bcshp,bcshq->bchpq", k_tail, vc)          # (b,nc,h,p,p)
    chunk_decay = jnp.exp(jnp.clip(lw[:, :, -1], -EXP_CLAMP, 0.0))  # (b,nc,h,p)

    def carry(S, inp):
        S_add, dec, r_blk, lwp_blk = inp
        # y_inter for this chunk uses S before update
        y_in = jnp.einsum("bthp,bhpq->bthq",
                          r_blk * jnp.exp(jnp.clip(lwp_blk, -EXP_CLAMP, 0.0)), S)
        S = S * dec[:, :, :, None] + S_add
        return S, y_in

    S_final, y_inter = jax.lax.scan(
        carry, state.astype(jnp.float32),
        (jnp.moveaxis(S_c, 1, 0), jnp.moveaxis(chunk_decay, 1, 0),
         jnp.moveaxis(rc, 1, 0), jnp.moveaxis(lw_prev, 1, 0)))
    y = y + jnp.moveaxis(y_inter, 0, 1)
    return y.reshape(b, s, h, p), S_final


def rwkv6_timemix(x, lp, cfg, state=None, prev=None, return_state=False):
    dims = rwkv6_dims(cfg)
    b, s, d = x.shape
    xs = _token_shift(x, prev)
    act = jnp.bfloat16 if cfg.ssm_bf16 else jnp.float32
    if cfg.fused_rwkv_proj:
        # y_i = x @ W_i + (mu_i*delta) @ W_i: read x and delta ONCE through a
        # stacked projection instead of 5 separate mixed-input matmuls (§Perf)
        delta = xs - x
        W = jnp.stack([lp["wr"], lp["wk"], lp["wv"], lp["wg"]])   # (4, d, d)
        mu = lp["tmix_mu"][:4].astype(jnp.float32)                # (4, d)
        W_mix = (mu[:, :, None] * W.astype(jnp.float32)).astype(W.dtype)
        if current_backend().is_ideal:
            base = jnp.einsum("bsd,idf->ibsf", x, W)
            mixp = jnp.einsum("bsd,idf->ibsf", delta, W_mix)
        else:
            base = jnp.stack([bmm(x, W[i]) for i in range(4)])
            mixp = jnp.stack([bmm(delta, W_mix[i]) for i in range(4)])
        rkvg = base + mixp
        r, k, v, gg = (rkvg[i].astype(act) for i in range(4))
        r = r.reshape(b, s, dims["h"], dims["p"])
        k = k.reshape(b, s, dims["h"], dims["p"])
        v = v.reshape(b, s, dims["h"], dims["p"])
        g = jax.nn.silu(gg.astype(jnp.float32)).astype(act)
        xw = x + lp["tmix_mu"][4][None, None].astype(x.dtype) * delta
    else:
        mix = lambda i: (x + lp["tmix_mu"][i][None, None].astype(x.dtype)
                         * (xs - x))
        xr, xk, xv, xw, xg = (mix(i) for i in range(5))
        r = bmm(xr, lp["wr"]).astype(act).reshape(b, s, dims["h"], dims["p"])
        k = bmm(xk, lp["wk"]).astype(act).reshape(b, s, dims["h"], dims["p"])
        v = bmm(xv, lp["wv"]).astype(act).reshape(b, s, dims["h"], dims["p"])
        g = jax.nn.silu(bmm(xg, lp["wg"]).astype(jnp.float32)).astype(act)
    w_log = -jnp.exp(lp["w_base"][None, None]
                     + bmm(jnp.tanh(bmm(xw, lp["w_lora_a"])
                                    .astype(jnp.float32)),
                           lp["w_lora_b"].astype(jnp.float32)))
    w_log = w_log.reshape(b, s, dims["h"], dims["p"])
    S0 = (jnp.zeros((b, dims["h"], dims["p"], dims["p"]), jnp.float32)
          if state is None else state)
    y, S = wkv6_chunked(r.astype(jnp.float32) if not cfg.ssm_bf16 else r,
                        k if cfg.ssm_bf16 else k.astype(jnp.float32),
                        v if cfg.ssm_bf16 else v.astype(jnp.float32),
                        w_log, lp["u"], S0, cfg.ssm_chunk or 64,
                        compute_dtype=act)
    y = y.reshape(b, s, d)
    y = rmsnorm(y.astype(jnp.bfloat16), lp["ln_x"]).astype(jnp.float32)
    out = bmm((y * g.astype(jnp.float32)).astype(jnp.bfloat16), lp["wo"])
    if return_state:
        return out, S, x[:, -1]
    return out


def rwkv6_channelmix(x, lp, prev=None, return_state=False):
    xs = _token_shift(x, prev)
    xk = x + lp["cmix_mu"][0][None, None].astype(x.dtype) * (xs - x)
    xr = x + lp["cmix_mu"][1][None, None].astype(x.dtype) * (xs - x)
    k = jnp.square(jax.nn.relu(bmm(xk, lp["ck"]).astype(jnp.float32)))
    kv = bmm(k.astype(jnp.bfloat16), lp["cv"])
    out = jax.nn.sigmoid(bmm(xr, lp["cr"]).astype(jnp.float32)).astype(kv.dtype) * kv
    if return_state:
        return out, x[:, -1]
    return out


def rwkv6_block(x, lp, cfg):
    h = rmsnorm(x, lp["norm_att"])
    x = x + rwkv6_timemix(h, lp, cfg)
    h = rmsnorm(x, lp["norm_ffn"])
    x = x + rwkv6_channelmix(h, lp)
    return shard(x, "batch", None, None)


def rwkv6_param_tree(cfg: ModelConfig) -> Params:
    return {**embed_param_specs(cfg),
            "blocks": rwkv6_param_specs(cfg),
            "final_norm": rmsnorm_spec(cfg.d_model)}


def rwkv6_loss(params, batch, cfg):
    x = embed(batch["tokens"], params)
    block = _remat(functools.partial(rwkv6_block, cfg=cfg), cfg)
    x = scan_layers(block, x, params["blocks"], unroll=cfg.unroll_layers)
    x = rmsnorm(x, params["final_norm"])
    return chunked_softmax_xent(x, params["embedding"], batch["labels"],
                                cfg.loss_chunk, unroll=cfg.unroll_layers)


def rwkv6_state_specs(cfg: ModelConfig, batch: int) -> Params:
    dims = rwkv6_dims(cfg)
    L = cfg.n_layers
    return {
        "wkv": ParamSpec((L, batch, dims["h"], dims["p"], dims["p"]),
                         jnp.float32, ("layers", "batch", "tp", None, None),
                         init="zeros"),
        "prev_att": ParamSpec((L, batch, cfg.d_model), jnp.bfloat16,
                              ("layers", "batch", None), init="zeros"),
        "prev_ffn": ParamSpec((L, batch, cfg.d_model), jnp.bfloat16,
                              ("layers", "batch", None), init="zeros"),
        "index": ParamSpec((batch,), jnp.int32, ("batch",), init="zeros"),
    }


def rwkv6_decode_step(params, state, tokens, cfg):
    x = embed(tokens, params)

    def body(carry, layer):
        x = carry
        lp, wkv, pa, pf = layer
        h = rmsnorm(x, lp["norm_att"])
        att, wkv_new, pa_new = rwkv6_timemix(h, lp, cfg, state=wkv, prev=pa,
                                             return_state=True)
        x = x + att
        h = rmsnorm(x, lp["norm_ffn"])
        ffn, pf_new = rwkv6_channelmix(h, lp, prev=pf, return_state=True)
        x = x + ffn
        return x, (wkv_new, pa_new, pf_new)

    x, (wkv, pa, pf) = scan_layers(
        body, x, (params["blocks"], state["wkv"], state["prev_att"],
                  state["prev_ffn"]), unroll=cfg.unroll_layers, collect=True)
    x = rmsnorm(x, params["final_norm"])
    logits = logits_last(x, params["embedding"])
    return logits, {"wkv": wkv, "prev_att": pa.astype(jnp.bfloat16),
                    "prev_ffn": pf.astype(jnp.bfloat16),
                    "index": state["index"] + 1}


# ===========================================================================
# Zamba2 hybrid
# ===========================================================================


def zamba2_param_tree(cfg: ModelConfig) -> Params:
    n_apps = cfg.n_layers // cfg.shared_attn_period
    shared = {
        "norm_attn": rmsnorm_spec(cfg.d_model),
        "norm_mlp": rmsnorm_spec(cfg.d_model),
        "attn": attention_param_specs(cfg, layers=0),
        "mlp": mlp_param_specs(cfg, layers=0),
        "down": ParamSpec((2 * cfg.d_model, cfg.d_model), jnp.bfloat16,
                          ("fsdp", "tp")),
    }
    return {**embed_param_specs(cfg),
            "mamba": mamba2_param_specs(cfg, cfg.n_layers),
            "shared": shared,
            "final_norm": rmsnorm_spec(cfg.d_model),
            }


def _zamba_shared_block(x, emb0, sp, cfg):
    """Shared attention block: concat(hidden, first-layer embedding) ->
    down-projection -> attn -> mlp (zamba2 concat re-use trick)."""
    cat = jnp.concatenate([x, emb0], axis=-1)
    h = bmm(cat, sp["down"])
    a = rmsnorm(h, sp["norm_attn"])
    h = h + attention(a, sp["attn"], cfg, causal=True)
    a = rmsnorm(h, sp["norm_mlp"])
    h = h + mlp(a, sp["mlp"], cfg)
    return x + h


def zamba2_loss(params, batch, cfg):
    x = embed(batch["tokens"], params)
    emb0 = x
    period = cfg.shared_attn_period
    n_groups = cfg.n_layers // period
    mamba = jax.tree.map(
        lambda a: a.reshape((n_groups, period) + a.shape[1:]), params["mamba"])

    def group(x, gp):
        inner_r = _remat(
            lambda c, lp: c + mamba2_forward(rmsnorm(c, lp["norm"]), lp, cfg),
            cfg)
        x = scan_layers(inner_r, x, gp, unroll=cfg.unroll_layers)
        x = _remat(lambda h: _zamba_shared_block(h, emb0, params["shared"],
                                                 cfg), cfg)(x)
        return x

    x = scan_layers(group, x, mamba, unroll=cfg.unroll_layers)
    x = rmsnorm(x, params["final_norm"])
    return chunked_softmax_xent(x, params["embedding"], batch["labels"],
                                cfg.loss_chunk, unroll=cfg.unroll_layers)


def zamba2_state_specs(cfg: ModelConfig, batch: int, max_len: int,
                       long_context: bool = False) -> Params:
    dims = mamba2_dims(cfg)
    L = cfg.n_layers
    n_apps = L // cfg.shared_attn_period
    seq_ax = "seq_full" if long_context else "seq_tp"
    return {
        "ssm": ParamSpec((L, batch, dims["n_heads"], dims["d_state"],
                          dims["p"]), jnp.float32,
                         ("layers", "batch", "tp", None, None), init="zeros"),
        "conv": ParamSpec((L, batch, 3, dims["conv_dim"]), jnp.bfloat16,
                          ("layers", "batch", None, "tp"), init="zeros"),
        "kv": {
            "k": ParamSpec((n_apps, batch, max_len, cfg.n_kv_heads, cfg.d_head),
                           jnp.bfloat16, ("layers", "batch", seq_ax, None, None),
                           init="zeros"),
            "v": ParamSpec((n_apps, batch, max_len, cfg.n_kv_heads, cfg.d_head),
                           jnp.bfloat16, ("layers", "batch", seq_ax, None, None),
                           init="zeros"),
        },
        "index": ParamSpec((batch,), jnp.int32, ("batch",), init="zeros"),
    }


def zamba2_decode_step(params, state, tokens, cfg):
    x = embed(tokens, params)
    emb0 = x
    period = cfg.shared_attn_period
    n_groups = cfg.n_layers // period
    index = state["index"]
    sp = params["shared"]
    # Every Mamba leaf stays the flat (n_layers, ...) stack, read one layer
    # at a time where it is used.  A (n_groups, period, ...) regroup makes
    # XLA slice out each group, and a loop nested in the group loop makes it
    # copy the whole in_proj stack out of its device layout: both per step.
    # So one scan over the groups, with the period's layers unrolled in its
    # body; the barrier keeps each layer's output a bf16 value, as a scan
    # carry is, so the step gives the same bits as a scan over the layers.
    stacks = (params["mamba"], state["ssm"], state["conv"])
    pin = (lambda a: a) if cfg.unroll_layers else jax.lax.optimization_barrier

    def group(x, inp):
        g, kv_l = inp

        def inner(x, j):
            lp, s1, c1 = layer_at(stacks, g * period + j)
            y, s2, c2 = mamba2_step(rmsnorm(x, lp["norm"]), lp, cfg, s1, c1)
            return pin(x + y), (s2, c2)

        x, (ssm_new, conv_new) = scan_layers(inner, x, np.arange(period),
                                             unroll=True, collect=True)
        # shared attention with its per-application KV cache
        cat = jnp.concatenate([x, emb0], axis=-1)
        h = bmm(cat, sp["down"])
        a = rmsnorm(h, sp["norm_attn"])
        att, kv_new = decode_attention(a, sp["attn"], cfg, kv_l, index)
        h = h + att
        a = rmsnorm(h, sp["norm_mlp"])
        h = h + mlp(a, sp["mlp"], cfg)
        return x + h, (ssm_new, conv_new, kv_new)

    x, (ssm, conv, kv) = scan_layers(
        group, x, (np.arange(n_groups), state["kv"]),
        unroll=cfg.unroll_layers, collect=True)
    flat = lambda a: a.reshape((-1,) + a.shape[2:])
    x = rmsnorm(x, params["final_norm"])
    logits = logits_last(x, params["embedding"])
    return logits, {"ssm": flat(ssm), "conv": flat(conv).astype(jnp.bfloat16),
                    "kv": kv, "index": index + 1}


# ===========================================================================
# Per-layer hybrids (``layer_types``: granite-4.0-h)
# ===========================================================================
#
# Each kind of block has one flat stack of weights (and of state), in layer
# order: "mamba2" -> params["mamba"] (state "ssm", "conv"), "attention" ->
# params["attn"] (state "kv"), "experts" -> params["moe"].  A layer is
#     for kind in layer_types[i]:  x = x + residual_multiplier * kind(norm(x))


_STACK = {"mamba2": "mamba", "attention": "attn", "experts": "moe"}


def hybrid_param_tree(cfg: ModelConfig) -> Params:
    n = {k: cfg.layer_count(k) for k in LAYER_KINDS}
    tree = {**embed_param_specs(cfg), "final_norm": rmsnorm_spec(cfg.d_model)}
    if n["mamba2"]:
        tree["mamba"] = mamba2_param_specs(cfg, n["mamba2"])
    if n["attention"]:
        tree["attn"] = {
            "norm": ParamSpec((n["attention"], cfg.d_model), jnp.float32,
                              ("layers", None), init="ones"),
            **attention_param_specs(cfg, layers=n["attention"])}
    if n["experts"]:
        tree["moe"] = held_experts_param_specs(cfg, n["experts"])
    return tree


def hybrid_state_specs(cfg: ModelConfig, batch: int, max_len: int,
                       long_context: bool = False) -> Params:
    """Mamba2 state of the Mamba2 layers, K/V of the attention layers."""
    dims = mamba2_dims(cfg)
    n = {k: cfg.layer_count(k) for k in LAYER_KINDS}
    specs = {"index": ParamSpec((batch,), jnp.int32, ("batch",),
                                init="zeros")}
    if n["mamba2"]:
        specs["ssm"] = ParamSpec(
            (n["mamba2"], batch, dims["n_heads"], dims["d_state"], dims["p"]),
            jnp.float32, ("layers", "batch", "tp", None, None), init="zeros")
        specs["conv"] = ParamSpec((n["mamba2"], batch, 3, dims["conv_dim"]),
                                  jnp.bfloat16, ("layers", "batch", None, "tp"),
                                  init="zeros")
    if n["attention"]:
        specs["kv"] = KVCacheSpec(
            n["attention"], batch, max_len, cfg.n_kv_heads, cfg.d_head,
            seq_axis="seq_full" if long_context else "seq_tp").specs()
    return specs


def _walk(x: jax.Array, params: Params, cfg: ModelConfig, block,
          carry=()) -> Tuple[jax.Array, Any]:
    """Every layer over ``x``: one scan over the periods of ``layer_types``
    with a period's layers unrolled in its body, each block's weights read
    in place from its flat stack (``layer_at``; a regrouped stack is copied
    on the chip).  ``block(kind, x, lp, i, carry)`` -> (branch, carry)
    gives a block's residual branch, ``i`` its index in its stack, and may
    update ``carry`` (the decode state's stacks, written in place)."""
    types, period = cfg.layer_types, cfg.layer_period
    per = {k: sum(k in t for t in types[:period]) for k in LAYER_KINDS}
    r = cfg.residual_multiplier

    def body(xc, p):
        x, carry = xc
        seen = collections.Counter()
        for kinds in types[:period]:
            for kind in kinds:
                i = p * per[kind] + seen[kind]
                seen[kind] += 1
                y, carry = block(kind, x, layer_at(params[_STACK[kind]], i),
                                 i, carry)
                x = x + r * y
        return x, carry

    return scan_layers(body, (x, carry), np.arange(len(types) // period),
                       unroll=cfg.unroll_layers)


def _put(stack, i, value):
    """``stack`` with layer ``i`` replaced by ``value`` (in place)."""
    return jax.tree.map(
        lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v.astype(a.dtype),
                                                         i, 0), stack, value)


def hybrid_hidden(params: Params, tokens: jax.Array,
                  cfg: ModelConfig) -> jax.Array:
    """Full-sequence forward: final-norm hidden states (b, s, d) over
    ``logits_scaling`` (logits are these against the tied embedding)."""
    eps = cfg.rms_norm_eps
    x = embed(tokens, params) * cfg.embedding_multiplier

    branches = {
        "mamba2": lambda x, lp: mamba2_forward(rmsnorm(x, lp["norm"], eps),
                                               lp, cfg),
        "attention": lambda x, lp: attention(rmsnorm(x, lp["norm"], eps), lp,
                                             cfg, causal=True),
        "experts": lambda x, lp: held_experts(x, lp, cfg, eps)[0]}

    def block(kind, x, lp, i, carry):
        return _remat(branches[kind], cfg)(x, lp), carry

    x, _ = _walk(x, params, cfg, block)
    return rmsnorm(x, params["final_norm"], eps) / cfg.logits_scaling


def hybrid_loss(params, batch, cfg):
    x = hybrid_hidden(params, batch["tokens"], cfg)
    return chunked_softmax_xent(x, params["embedding"], batch["labels"],
                                cfg.loss_chunk, unroll=cfg.unroll_layers)


def hybrid_decode_step(params, state, tokens, cfg):
    """One token a row.  A state that holds ``routed`` (``ModelAPI.
    counting_routes``) adds the step's expert routing to it."""
    eps = cfg.rms_norm_eps
    x = embed(tokens, params) * cfg.embedding_multiplier
    index = state["index"]

    def block(kind, x, lp, i, st):
        if kind == "experts":
            y, idx = held_experts(x, lp, cfg, eps)
            if "routed" in st:
                st = {**st, "routed": st["routed"] + route_counts(idx, cfg)}
            return y, st
        h = rmsnorm(x, lp["norm"], eps)
        if kind == "mamba2":
            y, s2, c2 = mamba2_step(h, lp, cfg,
                                    *layer_at((st["ssm"], st["conv"]), i))
            return y, {**st, "ssm": _put(st["ssm"], i, s2),
                       "conv": _put(st["conv"], i, c2)}
        y, kv = decode_attention(h, lp, cfg, layer_at(st["kv"], i), index)
        return y, {**st, "kv": _put(st["kv"], i, kv)}

    x, state = _walk(x, params, cfg, block, {**state, "index": index + 1})
    x = rmsnorm(x, params["final_norm"], eps)
    return logits_last(x, params["embedding"]) / cfg.logits_scaling, state


# ===========================================================================
# The two hybrid programs
# ===========================================================================


class HybridProgram(NamedTuple):
    param_tree: Callable
    state_specs: Callable
    loss: Callable
    decode_step: Callable


ZAMBA2 = HybridProgram(zamba2_param_tree, zamba2_state_specs, zamba2_loss,
                       zamba2_decode_step)
PER_LAYER = HybridProgram(hybrid_param_tree, hybrid_state_specs, hybrid_loss,
                          hybrid_decode_step)


def hybrid_program(cfg: ModelConfig) -> HybridProgram:
    """The program of a ``hybrid`` family config (``ModelConfig.per_layer``)."""
    return PER_LAYER if cfg.per_layer else ZAMBA2
