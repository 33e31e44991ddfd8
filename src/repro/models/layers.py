"""Shared model building blocks: norms, RoPE, GQA attention (train / prefill /
cached decode, causal + sliding-window), SwiGLU/GELU MLPs, MoE (dense dispatch
and expert-parallel all-to-all), and sequence-chunked cross-entropy.

Numerics policy: params bf16 (norm scales f32), matmuls bf16 with f32 softmax/
normalization/loss.  All activation sharding goes through shardlib.shard so
the same code serves every mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..backend import current_backend
from ..backend import matmul as bmm
from ..configs.base import ModelConfig
from .shardlib import ParamSpec, current_rules, shard

Params = Dict[str, Any]

NEG_INF = -2.0 ** 30   # large-but-finite mask value (avoids NaN from inf-inf)


def scan_layers(body, carry, stacked, unroll: bool = False,
                collect: bool = False):
    """lax.scan over a stacked layer pytree, or a python unroll when the
    caller needs cost_analysis to see every repetition (roofline estimator).

    body(carry, layer_tree) -> carry  (collect=False)
    body(carry, layer_tree) -> (carry, out)  (collect=True; outs stacked)
    """
    if not unroll:
        if collect:
            return jax.lax.scan(body, carry, stacked)
        return jax.lax.scan(lambda c, lp: (body(c, lp), ()), carry, stacked)[0]
    n = jax.tree.leaves(stacked)[0].shape[0]
    outs = []
    for i in range(n):
        lp = jax.tree.map(lambda a: a[i], stacked)
        if collect:
            carry, out = body(carry, lp)
            outs.append(out)
        else:
            carry = body(carry, lp)
    if collect:
        stacked_out = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        return carry, stacked_out
    return carry


def layer_at(stacked, i):
    """Layer ``i`` of every leaf of a stacked pytree, read in place: a
    static slice for a concrete index, a dynamic index for a traced one."""
    if isinstance(i, (int, np.integer)):
        return jax.tree.map(lambda a: a[i], stacked)
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stacked)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_spec(d: int) -> ParamSpec:
    return ParamSpec((d,), jnp.float32, (None,), init="ones")


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, d_head); positions: (..., seq)."""
    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, d/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attention_param_specs(cfg: ModelConfig, layers: Optional[int] = None) -> Params:
    """Stacked (layers-first) projection weights for the attention block."""
    L = cfg.n_layers if layers is None else layers
    lead = (L,) if L else ()
    lax = ("layers",) if L else ()
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    bf = jnp.bfloat16
    specs = {
        "wq": ParamSpec(lead + (d, qd), bf, lax + ("fsdp", "tp")),
        "wk": ParamSpec(lead + (d, kvd), bf, lax + ("fsdp", "tp")),
        "wv": ParamSpec(lead + (d, kvd), bf, lax + ("fsdp", "tp")),
        "wo": ParamSpec(lead + (qd, d), bf, lax + ("tp", "fsdp")),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec(lead + (qd,), bf, lax + ("tp",), init="zeros")
        specs["bk"] = ParamSpec(lead + (kvd,), bf, lax + ("tp",), init="zeros")
        specs["bv"] = ParamSpec(lead + (kvd,), bf, lax + ("tp",), init="zeros")
    return specs


def _qkv(x: jax.Array, p: Params, cfg: ModelConfig, positions: jax.Array):
    b, s, _ = x.shape
    q = bmm(x, p["wq"])
    k = bmm(x, p["wk"])
    v = bmm(x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.d_head)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(b, s, kv, d) -> (b, s, heads, d) by group repetition."""
    b, s, kv, d = k.shape
    if kv == n_heads:
        return k
    rep = n_heads // kv
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, rep, d))
    return k.reshape(b, s, n_heads, d)


def _mask(q_pos: jax.Array, k_pos: jax.Array, window: Optional[int],
          causal: bool) -> jax.Array:
    """(q, k) boolean keep-mask."""
    if causal:
        keep = k_pos[None, :] <= q_pos[:, None]
    else:
        keep = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if window is not None:
        keep &= k_pos[None, :] > (q_pos[:, None] - window)
    return keep


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, keep: jax.Array,
          d_head: int, scores_f32: bool = True) -> jax.Array:
    """q:(b,qs,h,d) k,v:(b,ks,h,d) keep:(qs,ks) -> (b,qs,h,d).  f32 softmax."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(d_head))
    scores = jnp.where(keep[None, None], scores, NEG_INF)
    if not scores_f32:
        # bf16 score pipeline: subtract the running max first so bf16's 8-bit
        # mantissa only ever sees bounded negatives (§Perf optimization)
        scores = (scores - jax.lax.stop_gradient(
            scores.max(-1, keepdims=True))).astype(jnp.bfloat16)
        w = jax.nn.softmax(scores.astype(jnp.bfloat16), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def _sdpa_grouped(q: jax.Array, k: jax.Array, v: jax.Array, keep: jax.Array,
                  d_head: int, n_kv: int,
                  scores_f32: bool = True) -> jax.Array:
    """GQA without materializing repeated K/V: q reshaped (b, qs, kv, g, d)
    einsummed against the raw (b, ks, kv, d) K/V (§Perf: removes the
    heads/kv_heads-fold byte inflation of _repeat_kv)."""
    b, qs, h, d = q.shape
    g = h // n_kv
    qg = q.reshape(b, qs, n_kv, g, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(d_head))
    scores = jnp.where(keep[None, None, None], scores, NEG_INF)
    if not scores_f32:
        scores = (scores - jax.lax.stop_gradient(
            scores.max(-1, keepdims=True))).astype(jnp.bfloat16)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w, v)
    return o.reshape(b, qs, h, d)


def attention(x: jax.Array, p: Params, cfg: ModelConfig,
              causal: bool = True,
              positions: Optional[jax.Array] = None,
              return_kv: bool = False):
    """Training/prefill attention, q-chunked to bound the (q, k) score tensor.

    Full sequence K/V stay resident; queries are processed in cfg.attn_chunk
    blocks via lax.map, so peak score memory is (b, h, chunk, s) instead of
    (b, h, s, s).  ``return_kv`` also yields the pre-repeat K/V for prefill
    cache construction (avoids re-projecting).
    """
    b, s, _ = x.shape
    pos = jnp.arange(s) if positions is None else positions
    q, k, v = _qkv(x, p, cfg, jnp.broadcast_to(pos, (b, s)))
    k_raw, v_raw = k, v
    q = shard(q, "batch", None, "tp", None)
    if not cfg.gqa_grouped:
        k = _repeat_kv(k, cfg.n_heads)
        v = _repeat_kv(v, cfg.n_heads)
    k = shard(k, "batch", None, "tp", None)
    v = shard(v, "batch", None, "tp", None)

    ch = min(cfg.attn_chunk, s)
    if s % ch:
        ch = s  # fall back to single chunk on awkward sizes
    n_chunk = s // ch
    k_pos = pos

    def one_chunk(ci):
        qc = jax.lax.dynamic_slice_in_dim(q, ci * ch, ch, axis=1)
        q_pos = jax.lax.dynamic_slice_in_dim(k_pos, ci * ch, ch, axis=0)
        keep = _mask(q_pos, k_pos, cfg.sliding_window, causal)
        if cfg.gqa_grouped:
            return _sdpa_grouped(qc, k, v, keep, cfg.d_head, cfg.n_kv_heads,
                                 cfg.attn_scores_f32)
        return _sdpa(qc, k, v, keep, cfg.d_head, cfg.attn_scores_f32)

    if n_chunk == 1:
        o = one_chunk(0)
    elif cfg.unroll_layers:
        o = jnp.stack([one_chunk(ci) for ci in range(n_chunk)])
        o = jnp.moveaxis(o, 0, 1).reshape(b, s, cfg.n_heads, cfg.d_head)
    else:
        o = jax.lax.map(one_chunk, jnp.arange(n_chunk))       # (n, b, ch, h, d)
        o = jnp.moveaxis(o, 0, 1).reshape(b, s, cfg.n_heads, cfg.d_head)
    o = o.reshape(b, s, cfg.q_dim)
    out = bmm(o, p["wo"])
    if return_kv:
        return out, k_raw, v_raw
    return out


# -- cached decode -----------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KVCacheSpec:
    """Decode-time KV cache layout: seq-sharded over the TP axis (flash-
    decoding style — XLA turns the softmax/output reductions over the sharded
    key axis into small all-reduces; see DESIGN.md Sec. 4).

    ``dtype_name='int8'`` stores symmetric-quantized K/V with per-(token,
    head) f32 scales — half the cache footprint/stream bytes (§Perf)."""

    layers: int
    batch: int
    max_len: int
    n_kv: int
    d_head: int
    dtype_name: str = "bf16"
    seq_axis: str = "seq_tp"

    def specs(self) -> Dict[str, ParamSpec]:
        shape = (self.layers, self.batch, self.max_len, self.n_kv, self.d_head)
        logical = ("layers", "batch", self.seq_axis, None, None)
        if self.dtype_name == "int8":
            sshape = shape[:-1] + (1,)
            return {
                "k": ParamSpec(shape, jnp.int8, logical, init="zeros"),
                "v": ParamSpec(shape, jnp.int8, logical, init="zeros"),
                "k_scale": ParamSpec(sshape, jnp.float32, logical,
                                     init="zeros"),
                "v_scale": ParamSpec(sshape, jnp.float32, logical,
                                     init="zeros"),
            }
        return {
            "k": ParamSpec(shape, jnp.bfloat16, logical, init="zeros"),
            "v": ParamSpec(shape, jnp.bfloat16, logical, init="zeros"),
        }


def _quant_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(..., dh) -> int8 payload + per-vector f32 scale."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def decode_attention(x: jax.Array, p: Params, cfg: ModelConfig,
                     kv: Dict[str, jax.Array],
                     index: jax.Array) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token attention against a cache.

    x: (b, 1, d); kv: {"k", "v"[, "k_scale", "v_scale"]} with k/v of shape
    (b, S, n_kv, dh); index: scalar position, or per-row (b,) positions —
    continuous batching runs every slot at its own offset, so each batch row
    writes its K/V at and masks against its own index.  Returns (out, new kv
    dict).
    """
    b = x.shape[0]
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (b,))
    pos = idx[:, None]
    q, k_new, v_new = _qkv(x, p, cfg, pos)
    int8 = "k_scale" in kv

    k_cache, v_cache = kv["k"], kv["v"]
    rows = jnp.arange(b)
    ring = (cfg.sliding_window is not None
            and k_cache.shape[1] <= cfg.sliding_window)
    slot = idx % k_cache.shape[1] if ring else idx   # ring buffer for SWA
    if int8:
        kq, ks = _quant_kv(k_new)
        vq, vs = _quant_kv(v_new)
        k_cache = k_cache.at[rows, slot].set(kq[:, 0])
        v_cache = v_cache.at[rows, slot].set(vq[:, 0])
        k_scale = kv["k_scale"].at[rows, slot].set(ks[:, 0])
        v_scale = kv["v_scale"].at[rows, slot].set(vs[:, 0])
        k_full = (k_cache.astype(jnp.float32) * k_scale).astype(jnp.bfloat16)
        v_full = (v_cache.astype(jnp.float32) * v_scale).astype(jnp.bfloat16)
        new_kv = {"k": k_cache, "v": v_cache,
                  "k_scale": k_scale, "v_scale": v_scale}
    else:
        k_cache = k_cache.at[rows, slot].set(k_new[:, 0])
        v_cache = v_cache.at[rows, slot].set(v_new[:, 0])
        k_full, v_full = k_cache, v_cache
        new_kv = {"k": k_cache, "v": v_cache}

    k = _repeat_kv(k_full, cfg.n_heads)
    v = _repeat_kv(v_full, cfg.n_heads)
    s = k.shape[1]
    k_pos = jnp.arange(s)
    if ring:
        # ring: everything valid once the row has wrapped
        valid = (k_pos[None, :] <= slot[:, None]) | (idx[:, None] >= s)
    else:
        valid = k_pos[None, :] <= idx[:, None]
        if cfg.sliding_window is not None:
            valid &= k_pos[None, :] > idx[:, None] - cfg.sliding_window
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(cfg.d_head))
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, 1, cfg.q_dim)
    return bmm(o, p["wo"]), new_kv


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_param_specs(cfg: ModelConfig, layers: Optional[int] = None,
                    d_ff: Optional[int] = None) -> Params:
    L = cfg.n_layers if layers is None else layers
    lead = (L,) if L else ()
    lax = ("layers",) if L else ()
    d = cfg.d_model
    ff = cfg.d_ff if d_ff is None else d_ff
    bf = jnp.bfloat16
    specs = {
        "w1": ParamSpec(lead + (d, ff), bf, lax + ("fsdp", "tp")),
        "w2": ParamSpec(lead + (ff, d), bf, lax + ("tp", "fsdp")),
    }
    if cfg.act == "swiglu":
        specs["wg"] = ParamSpec(lead + (d, ff), bf, lax + ("fsdp", "tp"))
    return specs


def mlp(x: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    if cfg.act == "swiglu":
        h = jax.nn.silu(bmm(x, p["wg"]).astype(jnp.float32)).astype(x.dtype)
        h = h * bmm(x, p["w1"])
    else:
        h = jax.nn.gelu(bmm(x, p["w1"]).astype(jnp.float32)).astype(x.dtype)
    h = shard(h, "batch", None, "tp")
    return bmm(h, p["w2"])


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


def moe_param_specs(cfg: ModelConfig, layers: Optional[int] = None) -> Params:
    L = cfg.n_layers if layers is None else layers
    lead = (L,) if L else ()
    lax = ("layers",) if L else ()
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    bf = jnp.bfloat16
    if cfg.moe_shard == "expert":
        # experts over the TP axis (llama4: 16 experts == 16-way model axis)
        in_ax = lax + ("expert", "fsdp", None)
        out_ax = lax + ("expert", None, "fsdp")
    else:
        # experts replicated across TP, FFN hidden sharded (grok: 8 experts)
        in_ax = lax + (None, "fsdp", "tp")
        out_ax = lax + (None, "tp", "fsdp")
    specs = {
        "router": ParamSpec(lead + (d, e), jnp.float32, lax + ("fsdp", None)),
        "w1": ParamSpec(lead + (e, d, ff), bf, in_ax),
        "w2": ParamSpec(lead + (e, ff, d), bf, out_ax),
    }
    if cfg.act == "swiglu":
        specs["wg"] = ParamSpec(lead + (e, d, ff), bf, in_ax)
    return specs


def _router(x: jax.Array, p: Params, cfg: ModelConfig):
    """Top-k routing. Returns (weights (t, k), indices (t, k)) over flat tokens."""
    logits = bmm(x.astype(jnp.float32), p["router"])          # (t, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w, idx, probs


def moe_dense(x: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    """Dense dispatch: every expert computes every token, gated combine.

    Paper-faithful to 'dropless' MoE semantics; compute cost is E/top_k x the
    active-expert FLOPs — visible in the roofline MODEL_FLOPS ratio and the
    target of the ep_a2a hillclimb (EXPERIMENTS.md §Perf).
    """
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    w, idx, _ = _router(xt, p, cfg)
    gates = jnp.zeros((t, cfg.n_experts), jnp.float32)
    gates = gates.at[jnp.arange(t)[:, None], idx].set(w)      # (t, E)
    if current_backend().is_ideal:
        # lint: allow=RP001 ideal-only fast path; non-ideal branch below bmm's
        up = lambda wkey: jnp.einsum("td,edf->etf", xt, p[wkey])
        down = lambda h: jnp.einsum("etf,efd->etd", h, p["w2"])  # lint: allow=RP001 ideal-only
    else:
        # per-expert GEMMs through the active backend (E dense matmuls)
        up = lambda wkey: jnp.stack(
            [bmm(xt, p[wkey][e]) for e in range(cfg.n_experts)])
        down = lambda h: jnp.stack(
            [bmm(h[e], p["w2"][e]) for e in range(cfg.n_experts)])
    if cfg.act == "swiglu":
        h = jax.nn.silu(up("wg").astype(jnp.float32)).astype(xt.dtype)
        h = h * up("w1")
    else:
        h = jax.nn.gelu(up("w1").astype(jnp.float32)).astype(xt.dtype)
    y = down(h)                                               # (E, t, d)
    out = jnp.einsum("etd,te->td", y, gates.astype(y.dtype))
    return out.reshape(b, s, d)


def moe_ep_a2a(x: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    """Expert-parallel MoE with all-to-all dispatch (shard_map).

    Requires n_experts == size of the 'tp'/'expert' mesh axis.  Tokens are
    bucketed into per-expert capacity buffers locally, exchanged with a tiled
    all_to_all, processed by the resident expert, and returned.  Capacity
    C = ceil(T_local * top_k / E * capacity_factor); overflow tokens fall back
    to zero contribution (standard Switch-style dropping).
    """
    rules = current_rules()
    mesh = rules.mesh
    axis = rules.table.get("expert")
    if mesh is None or axis is None:
        return moe_dense(x, p, cfg)            # no mesh: smoke-test fallback
    e_axis = axis if isinstance(axis, str) else axis[0]
    esize = mesh.shape[e_axis]
    if cfg.n_experts != esize:
        raise ValueError(
            f"ep_a2a needs n_experts == mesh['{e_axis}'] ({cfg.n_experts} vs "
            f"{esize}); use moe_impl='dense'")

    b, s, d = x.shape
    batch_axes = rules.table["batch"]
    fsdp_axes = rules.table["fsdp"]

    def local(xl, router, wg, w1, w2):
        # xl: (b_local, s_local, d); expert weights: (1, d, ff) local shard
        bl, sl = xl.shape[0], xl.shape[1]
        t = bl * sl
        xt = xl.reshape(t, d)
        wgt, idx, _ = _router(xt, {"router": router}, cfg)
        cap = int(t * cfg.top_k / cfg.n_experts * cfg.capacity_factor + 1)
        # position of each (token, k) among its expert's claims
        onehot = jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.int32)  # (t,k,E)
        flat = onehot.reshape(t * cfg.top_k, cfg.n_experts)
        pos = jnp.cumsum(flat, axis=0) * flat - 1              # rank within expert
        pos_tk = pos.reshape(t, cfg.top_k, cfg.n_experts)
        expert_pos = (pos_tk * onehot).sum(-1)                 # (t, k)
        keep = expert_pos < cap
        # scatter tokens into (E, cap, d) send buffer
        buf = jnp.zeros((cfg.n_experts, cap, d), xl.dtype)
        e_idx = idx.reshape(-1)
        c_idx = jnp.where(keep, expert_pos, cap - 1).reshape(-1)
        src = jnp.repeat(xt, cfg.top_k, axis=0)
        src = jnp.where(keep.reshape(-1, 1), src, 0)
        buf = buf.at[e_idx, c_idx].add(src)
        # exchange: (E, cap, d) -> each device gets its expert's tokens from all
        recv = jax.lax.all_to_all(buf, e_axis, split_axis=0, concat_axis=0,
                                  tiled=True)                  # (E*cap, d) worth
        recv = recv.reshape(cfg.n_experts * cap, d)
        # resident expert FFN (weights arrive as (1, d, ff) shards)
        if cfg.act == "swiglu":
            h = jax.nn.silu(bmm(recv, wg[0]).astype(jnp.float32)).astype(recv.dtype)
            h = h * bmm(recv, w1[0])
        else:
            h = jax.nn.gelu(bmm(recv, w1[0]).astype(jnp.float32)).astype(recv.dtype)
        y = bmm(h, w2[0])
        y = y.reshape(cfg.n_experts, cap, d)
        back = jax.lax.all_to_all(y, e_axis, split_axis=0, concat_axis=0,
                                  tiled=True).reshape(cfg.n_experts, cap, d)
        # gather each (token, k) result and combine with router weights
        out_tk = back[e_idx, c_idx].reshape(t, cfg.top_k, d)
        out_tk = jnp.where(keep[..., None], out_tk, 0)
        out = (out_tk * wgt[..., None].astype(out_tk.dtype)).sum(1)
        return out.reshape(bl, sl, d)

    # tokens are partitioned over BOTH the batch (data) and sequence (expert/
    # model) axes before dispatch — otherwise every model-column would
    # redundantly dispatch and compute the same tokens (measured 16x waste;
    # EXPERIMENTS.md §Perf cell D)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(batch_axes, e_axis, None),
                  P(None, None),                 # router replicated locally
                  P(e_axis, None, None), P(e_axis, None, None),
                  P(e_axis, None, None)),
        out_specs=P(batch_axes, e_axis, None), check_vma=False)
    wg = p.get("wg", p["w1"])
    return fn(x, p["router"], wg, p["w1"], p["w2"])


def moe(x: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    if cfg.moe_impl == "ep_a2a":
        return moe_ep_a2a(x, p, cfg)
    return moe_dense(x, p, cfg)


# ---------------------------------------------------------------------------
# Embedding / loss
# ---------------------------------------------------------------------------


def embed_param_specs(cfg: ModelConfig) -> Params:
    return {"embedding": ParamSpec((cfg.padded_vocab, cfg.d_model), jnp.bfloat16,
                                   ("tp", "fsdp"), init="embed")}


def embed(tokens: jax.Array, p: Params) -> jax.Array:
    x = jnp.take(p["embedding"], tokens, axis=0)
    return shard(x, "batch", None, None)


def chunked_softmax_xent(x: jax.Array, emb: jax.Array, labels: jax.Array,
                         chunk: int = 256, unroll: bool = False) -> jax.Array:
    """Sequence-chunked cross-entropy against the (tied) unembedding.

    Never materialises the full (b, s, V) logits: chunks of `chunk` positions
    produce (b, chunk, V) logits (vocab TP-sharded), reduce to scalar loss and
    are discarded inside the scan.  Measured on qwen1.5-110b this removes a
    ~40 GiB/device temp buffer (DESIGN.md Sec. 4)."""
    b, s, d = x.shape
    ch = min(chunk, s)
    if s % ch:
        ch = s
    n = s // ch

    def body(acc, ci):
        xc = jax.lax.dynamic_slice_in_dim(x, ci * ch, ch, axis=1)
        yc = jax.lax.dynamic_slice_in_dim(labels, ci * ch, ch, axis=1)
        logits = bmm(xc, emb.T).astype(jnp.float32)            # (b, ch, V)
        logits = shard(logits, "batch", None, "tp")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return acc + (lse - gold).sum(), ()

    if n == 1:
        loss, _ = body(jnp.float32(0), 0)
    elif unroll:
        loss = jnp.float32(0)
        for ci in range(n):
            loss, _ = body(loss, ci)
    else:
        loss, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(n))
    return loss / (b * s)


def logits_last(x_last: jax.Array, emb: jax.Array) -> jax.Array:
    """(b, 1, d) -> (b, V) logits for decode."""
    out = bmm(x_last[:, 0], emb.T).astype(jnp.float32)
    return shard(out, "batch", "tp")
