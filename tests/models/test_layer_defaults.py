"""The attention and norm options granite-4.0-h added, at their defaults,
change nothing: for the phi4 and zamba2 smoke configurations, attention,
cached decode attention and RMSNorm give the same bits as the code before
the options (RoPE, scores over sqrt(d_head), epsilon 1e-6); without position
encoding or with a set scale the results differ."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.layers import (NEG_INF, _repeat_kv, apply_rope, attention,
                                 attention_param_specs, decode_attention,
                                 rmsnorm)
from repro.models.shardlib import init_param_tree

ARCHS = ["phi4-mini-3.8b", "zamba2-2.7b"]
KEY = jax.random.PRNGKey(3)


def _qkv_before(x, p, cfg, pos):
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, cfg.d_head)
    k = (x @ p["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = (x @ p["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    return (apply_rope(q, pos, cfg.rope_theta),
            apply_rope(k, pos, cfg.rope_theta), v)


def _softmax_out(q, k, v, keep, cfg, p):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, _repeat_kv(k, cfg.n_heads))
    scores = scores.astype(jnp.float32) / jnp.sqrt(jnp.float32(cfg.d_head))
    scores = jnp.where(keep, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, _repeat_kv(v, cfg.n_heads))
    return o.reshape(q.shape[0], q.shape[1], cfg.q_dim) @ p["wo"]


def attention_before(x, p, cfg):
    """Causal attention as it was: RoPE, scores over sqrt(d_head)."""
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _qkv_before(x, p, cfg, pos)
    keep = jnp.tril(jnp.ones((s, s), bool))[None, None]
    return _softmax_out(q, k, v, keep, cfg, p)


def decode_attention_before(x, p, cfg, kv, index):
    b = x.shape[0]
    q, k1, v1 = _qkv_before(x, p, cfg, index[:, None])
    rows = jnp.arange(b)
    k = kv["k"].at[rows, index].set(k1[:, 0])
    v = kv["v"].at[rows, index].set(v1[:, 0])
    keep = (jnp.arange(k.shape[1])[None, :] <= index[:, None])[:, None, None]
    return _softmax_out(q, k, v, keep, cfg, p), {"k": k, "v": v}


def _case(arch, seq=12):
    cfg = get_config(arch, smoke=True)
    p = init_param_tree(KEY, attention_param_specs(cfg, layers=0))
    x = jax.random.normal(KEY, (2, seq, cfg.d_model)).astype(jnp.bfloat16)
    return cfg, p, x


@pytest.mark.parametrize("arch", ARCHS)
def test_defaults_are_the_options_of_before(arch):
    cfg = get_config(arch, smoke=True)
    assert (cfg.position_embedding_type, cfg.attention_multiplier,
            cfg.rms_norm_eps) == ("rope", None, 1e-6)


@pytest.mark.parametrize("arch", ARCHS)
def test_attention_gives_the_bits_of_before(arch):
    cfg, p, x = _case(arch)
    with jax.disable_jit():
        got = attention(x, p, cfg, causal=True)
        want = attention_before(x, p, cfg)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_attention_gives_the_bits_of_before(arch):
    cfg, p, x = _case(arch, seq=1)
    shape = (2, 16, cfg.n_kv_heads, cfg.d_head)
    kv = {"k": jax.random.normal(KEY, shape).astype(jnp.bfloat16),
          "v": jax.random.normal(jax.random.PRNGKey(4), shape)
          .astype(jnp.bfloat16)}
    index = jnp.asarray([3, 9], jnp.int32)
    with jax.disable_jit():
        got, got_kv = decode_attention(x, p, cfg, kv, index)
        want, want_kv = decode_attention_before(x, p, cfg, kv, index)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got_kv[name], np.float32),
                                      np.asarray(want_kv[name], np.float32))


@pytest.mark.parametrize("arch", ARCHS)
def test_rmsnorm_gives_the_bits_of_before(arch):
    cfg = get_config(arch, smoke=True)
    x = jax.random.normal(KEY, (3, cfg.d_model)).astype(jnp.bfloat16)
    scale = jax.random.uniform(KEY, (cfg.d_model,), jnp.float32, 0.5, 1.5)
    xf = x.astype(jnp.float32)
    want = (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
            * scale).astype(x.dtype)
    for got in (rmsnorm(x, scale), rmsnorm(x, scale, cfg.rms_norm_eps)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    assert not np.array_equal(np.asarray(rmsnorm(x * 1e-3, scale, 1e-5)),
                              np.asarray(rmsnorm(x * 1e-3, scale)))


@pytest.mark.parametrize("arch", ARCHS)
def test_no_position_encoding_and_a_set_scale_differ(arch):
    cfg, p, x = _case(arch)
    base = np.asarray(attention(x, p, cfg), np.float32)
    nope = dataclasses.replace(cfg, position_embedding_type="nope")
    scaled = dataclasses.replace(cfg, attention_multiplier=1 / cfg.d_head)
    for other in (nope, scaled):
        assert not np.allclose(np.asarray(attention(x, p, other), np.float32),
                               base, atol=1e-3)
    # without RoPE the keys of every position are alike but for the token:
    # a sequence of one token repeated attends uniformly, with or without it
    same = jnp.broadcast_to(x[:, :1], x.shape)
    with jax.disable_jit():
        out = attention(same, p, nope)
    np.testing.assert_allclose(np.asarray(out[:, 1:], np.float32),
                               np.asarray(jnp.broadcast_to(
                                   out[:, :1], out[:, 1:].shape), np.float32),
                               atol=1e-2)
