"""Cross-path consistency: chunked/parallel training forms vs recurrent decode
forms must agree; chunked losses vs naive; masks behave causally."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backend import matmul as bmm
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.models import model_api
from repro.models import ssm as S
from repro.models.layers import (attention, attention_param_specs,
                                 chunked_softmax_xent, decode_attention, embed,
                                 logits_last, mlp, rmsnorm, scan_layers)
from repro.models.shardlib import init_param_tree

KEY = jax.random.PRNGKey(42)


def _zero_state(api, shape):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        api.decode_state_specs(shape),
                        is_leaf=lambda x: hasattr(x, "struct"))


def _decode_all(api, params, toks):
    T = toks.shape[1]
    state = _zero_state(api, ShapeConfig("t", T, toks.shape[0], "decode"))
    step = jax.jit(api.decode_step)
    lg = None
    for t in range(T):
        lg, state = step(params, state, toks[:, t:t + 1])
    return lg


@pytest.mark.parametrize("arch", ["rwkv6-1.6b", "zamba2-2.7b", "phi4-mini-3.8b",
                                  "llava-next-mistral-7b",
                                  "granite-4.0-h-small"])
def test_decode_matches_parallel_forward(arch):
    """Running the prompt token-by-token through decode_step must produce the
    same last-position logits as the parallel (training) forward."""
    cfg = get_config(arch, smoke=True)
    api = model_api(cfg)
    params = api.init_params(KEY)
    T = 8
    toks = jax.random.randint(KEY, (1, T), 0, cfg.vocab_size)

    if cfg.family == "vlm":
        # compare text-only: patch prefix empty not supported -> skip frontend
        import dataclasses
        cfg = dataclasses.replace(cfg, frontend=None)
        api = model_api(cfg)
    batch = {"tokens": toks, "labels": toks}

    # parallel: reuse the loss path's backbone by asking for last logits
    if cfg.family in ("dense", "moe", "vlm"):
        from repro.models import lm
        x = embed(toks, params)
        y = lm.backbone(params, x, cfg)
        full = logits_last(y[:, -1:], params["embedding"])
    elif cfg.family == "ssm":
        x = embed(toks, params)
        x, _ = jax.lax.scan(lambda c, lp: (S.rwkv6_block(c, lp, cfg), ()), x,
                            params["blocks"])
        full = logits_last(rmsnorm(x, params["final_norm"])[:, -1:],
                           params["embedding"])
    elif cfg.per_layer:  # per-layer hybrid: the loss path's forward
        full = logits_last(S.hybrid_hidden(params, toks, cfg)[:, -1:],
                           params["embedding"])
    else:  # hybrid: recompute via the loss path pieces
        from repro.models.layers import chunked_softmax_xent  # noqa
        x = embed(toks, params)
        emb0 = x
        period = cfg.shared_attn_period
        n_groups = cfg.n_layers // period
        mamba = jax.tree.map(
            lambda a: a.reshape((n_groups, period) + a.shape[1:]),
            params["mamba"])

        def group(x, gp):
            def inner(c, lp):
                return c + S.mamba2_forward(rmsnorm(c, lp["norm"]), lp, cfg), ()
            x, _ = jax.lax.scan(inner, x, gp)
            x = S._zamba_shared_block(x, emb0, params["shared"], cfg)
            return x, ()

        x, _ = jax.lax.scan(group, x, mamba)
        full = logits_last(rmsnorm(x, params["final_norm"])[:, -1:],
                           params["embedding"])

    dec = _decode_all(api, params, toks)
    scale = float(jnp.abs(full).max()) + 1e-9
    err = float(jnp.abs(dec - full).max()) / scale
    assert err < 2e-2, f"{arch}: decode/parallel mismatch {err}"


def _regrouped_zamba2_decode_step(params, state, tokens, cfg):
    """The hybrid decode step as it was before it read each layer in place:
    every Mamba leaf and state regrouped to (n_groups, period, ...) and
    scanned group by group.  The oracle for the in-place step."""
    x = embed(tokens, params)
    emb0 = x
    period = cfg.shared_attn_period
    n_groups = cfg.n_layers // period
    regroup = lambda a: a.reshape((n_groups, period) + a.shape[1:])
    mamba = jax.tree.map(regroup, params["mamba"])
    index = state["index"]
    sp = params["shared"]

    def group(x, inp):
        gp, ssm_s, conv_s, kv_l = inp

        def inner(x, layer):
            lp, s1, c1 = layer
            y, s2, c2 = S.mamba2_step(rmsnorm(x, lp["norm"]), lp, cfg, s1, c1)
            return x + y, (s2, c2)

        x, (ssm_new, conv_new) = scan_layers(inner, x, (gp, ssm_s, conv_s),
                                             unroll=cfg.unroll_layers,
                                             collect=True)
        cat = jnp.concatenate([x, emb0], axis=-1)
        h = bmm(cat, sp["down"])
        a = rmsnorm(h, sp["norm_attn"])
        att, kv_new = decode_attention(a, sp["attn"], cfg, kv_l, index)
        h = h + att
        a = rmsnorm(h, sp["norm_mlp"])
        h = h + mlp(a, sp["mlp"], cfg)
        return x + h, (ssm_new, conv_new, kv_new)

    x, (ssm, conv, kv) = scan_layers(
        group, x, (mamba, regroup(state["ssm"]), regroup(state["conv"]),
                   state["kv"]), unroll=cfg.unroll_layers, collect=True)
    flat = lambda a: a.reshape((-1,) + a.shape[2:])
    x = rmsnorm(x, params["final_norm"])
    logits = logits_last(x, params["embedding"])
    return logits, {"ssm": flat(ssm), "conv": flat(conv).astype(jnp.bfloat16),
                    "kv": kv, "index": index + 1}


@pytest.mark.parametrize("unroll", [False, True])
def test_zamba2_decode_step_matches_regrouped_oracle(unroll):
    """Reading each Mamba layer in place gives the same bits as the
    regrouped step: logits and every state leaf, over several steps."""
    import dataclasses
    cfg = dataclasses.replace(get_config("zamba2-2.7b", smoke=True),
                              unroll_layers=unroll)
    api = model_api(cfg)
    params = api.init_params(KEY)
    toks = jax.random.randint(KEY, (2, 5), 0, cfg.vocab_size)
    new = jax.jit(api.decode_step)
    old = jax.jit(lambda p, s, t: _regrouped_zamba2_decode_step(p, s, t, cfg))
    s_new = s_old = _zero_state(api, ShapeConfig("t", 8, 2, "decode"))
    for t in range(toks.shape[1]):
        lg_new, s_new = new(params, s_new, toks[:, t:t + 1])
        lg_old, s_old = old(params, s_old, toks[:, t:t + 1])
        np.testing.assert_array_equal(np.asarray(lg_new), np.asarray(lg_old))
        assert jax.tree.structure(s_new) == jax.tree.structure(s_old)
        for a, b in zip(jax.tree.leaves(s_new), jax.tree.leaves(s_old)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


def test_zamba2_decode_step_reads_layers_in_place():
    """The compiled hybrid decode step holds one layer's Mamba weights at a
    time: no (period, ...) group of a weight stack, nor a stack regrouped
    to (n_groups, period, ...), is ever a value of the program."""
    import dataclasses
    import re
    cfg = dataclasses.replace(get_config("zamba2-2.7b", smoke=True),
                              n_layers=6, shared_attn_period=3)
    api = model_api(cfg)
    dims = S.mamba2_dims(cfg)
    d, period = cfg.d_model, cfg.shared_attn_period
    n_groups = cfg.n_layers // period
    params = jax.eval_shape(api.init_params, KEY)
    state = jax.eval_shape(
        lambda: _zero_state(api, ShapeConfig("t", 8, 1, "decode")))
    toks = jax.ShapeDtypeStruct((1, 1), jnp.int32)
    hlo = jax.jit(api.decode_step).lower(params, state, toks).compile().as_text()
    shapes = {tuple(int(n) for n in m.split(",")) for m in
              re.findall(r"\b(?:bf16|f32)\[([0-9,]+)\]", hlo)}
    for w in ((d, dims["in_dim"]), (dims["d_inner"], d)):   # in_proj, out_proj
        for lead in ((period,), (n_groups, period)):
            for one in ((), (1,)):
                assert one + lead + w not in shapes, one + lead + w
        assert {w, (1,) + w} & shapes, f"no single-layer slice {w}"


def test_prefill_matches_decode_path():
    """prefill(prompt) then decode_step(next) == decoding everything."""
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    api = model_api(cfg)
    params = api.init_params(KEY)
    toks = jax.random.randint(KEY, (2, 9), 0, cfg.vocab_size)
    lg_pref, state = jax.jit(
        lambda p, b: api.prefill(p, b, max_len=9))(params, {"tokens": toks[:, :8]})
    lg_dec = _decode_all(api, params, toks[:, :8])
    scale = float(jnp.abs(lg_dec).max()) + 1e-9
    assert float(jnp.abs(lg_pref - lg_dec).max()) / scale < 2e-2


# ---------------------------------------------------------------------------
# oracle tests for the recurrence building blocks
# ---------------------------------------------------------------------------


def _naive_wkv(r, k, v, w_log, u, state):
    b, s, h, p = r.shape
    S_ = np.array(state, np.float64)
    w = np.exp(np.array(w_log, np.float64))
    r, k, v = (np.array(a, np.float64) for a in (r, k, v))
    u = np.array(u, np.float64)
    ys = np.zeros((b, s, h, p))
    for t in range(s):
        kv = np.einsum("bhp,bhq->bhpq", k[:, t], v[:, t])
        ys[:, t] = np.einsum("bhp,bhpq->bhq", r[:, t],
                             S_ + u[None, :, :, None] * kv)
        S_ = S_ * w[:, t][..., None] + kv
    return ys, S_


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_wkv6_chunked_matches_naive(chunk):
    b, s, h, p = 2, 16, 3, 8
    key = jax.random.PRNGKey(chunk)
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (b, s, h, p))
    k = jax.random.normal(ks[1], (b, s, h, p))
    v = jax.random.normal(ks[2], (b, s, h, p))
    w_log = -jnp.exp(jax.random.normal(ks[3], (b, s, h, p)) * 0.5)
    u = jax.random.normal(ks[4], (h, p)) * 0.1
    S0 = jnp.zeros((b, h, p, p))
    y, s_out = S.wkv6_chunked(r, k, v, w_log, u, S0, chunk)
    y_ref, s_ref = _naive_wkv(r, k, v, w_log, u, S0)
    np.testing.assert_allclose(np.array(y), y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.array(s_out), s_ref, rtol=2e-4, atol=2e-4)


def test_mamba2_chunk_invariance():
    """SSD output must not depend on the chunk size."""
    import dataclasses
    cfg = get_config("zamba2-2.7b", smoke=True)
    api = model_api(cfg)
    params = api.init_params(KEY)
    lp = jax.tree.map(lambda a: a[0], params["mamba"])
    x = jax.random.normal(KEY, (2, 32, cfg.d_model)).astype(jnp.bfloat16)
    outs = []
    for ch in (4, 8, 32):
        c2 = dataclasses.replace(cfg, ssm_chunk=ch)
        outs.append(np.array(S.mamba2_forward(x, lp, c2), np.float32))
    np.testing.assert_allclose(outs[0], outs[1], rtol=3e-2, atol=3e-3)
    np.testing.assert_allclose(outs[0], outs[2], rtol=3e-2, atol=3e-3)


def test_mamba2_forward_matches_step():
    cfg = get_config("zamba2-2.7b", smoke=True)
    api = model_api(cfg)
    params = api.init_params(KEY)
    lp = jax.tree.map(lambda a: a[0], params["mamba"])
    dims = S.mamba2_dims(cfg)
    T = 6
    x = jax.random.normal(KEY, (1, T, cfg.d_model)).astype(jnp.bfloat16) * 0.3
    y_par = np.array(S.mamba2_forward(x, lp, cfg), np.float32)
    ssm_state = jnp.zeros((1, dims["n_heads"], dims["d_state"], dims["p"]))
    conv_state = jnp.zeros((1, 3, dims["conv_dim"]), jnp.bfloat16)
    ys = []
    for t in range(T):
        y, ssm_state, conv_state = S.mamba2_step(x[:, t:t + 1], lp, cfg,
                                                 ssm_state, conv_state)
        ys.append(np.array(y, np.float32)[:, 0])
    y_seq = np.stack(ys, axis=1)
    np.testing.assert_allclose(y_seq, y_par, rtol=5e-2, atol=5e-3)


# ---------------------------------------------------------------------------
# attention / loss properties
# ---------------------------------------------------------------------------


def test_chunked_xent_matches_naive():
    b, s, d, v = 2, 12, 16, 40
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (b, s, d), jnp.float32).astype(jnp.bfloat16)
    emb = jax.random.normal(key, (v, d), jnp.float32).astype(jnp.bfloat16)
    labels = jax.random.randint(key, (b, s), 0, v)
    for chunk in (3, 4, 12, 100):
        got = chunked_softmax_xent(x, emb, labels, chunk=chunk)
        logits = (x @ emb.T).astype(jnp.float32)
        ref = (jax.nn.logsumexp(logits, -1)
               - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0])
        np.testing.assert_allclose(float(got), float(ref.mean()), rtol=1e-5)


def test_attention_is_causal():
    """Future tokens must not influence earlier positions."""
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    specs = attention_param_specs(cfg, layers=0)
    p = init_param_tree(KEY, specs)
    x1 = jax.random.normal(KEY, (1, 8, cfg.d_model)).astype(jnp.bfloat16)
    x2 = x1.at[:, 5:].set(jax.random.normal(
        jax.random.PRNGKey(9), (1, 3, cfg.d_model)).astype(jnp.bfloat16))
    y1 = attention(x1, p, cfg, causal=True)
    y2 = attention(x2, p, cfg, causal=True)
    np.testing.assert_allclose(np.array(y1[:, :5], np.float32),
                               np.array(y2[:, :5], np.float32), atol=1e-6)
    assert not np.allclose(np.array(y1[:, 5:], np.float32),
                           np.array(y2[:, 5:], np.float32))


def test_sliding_window_mask():
    """With window w, token t must ignore keys <= t - w."""
    import dataclasses
    cfg = dataclasses.replace(get_config("llava-next-mistral-7b", smoke=True),
                              frontend=None, sliding_window=4)
    specs = attention_param_specs(cfg, layers=0)
    p = init_param_tree(KEY, specs)
    x1 = jax.random.normal(KEY, (1, 12, cfg.d_model)).astype(jnp.bfloat16)
    # perturb position 0: outputs at positions >= 4 must be unchanged
    x2 = x1.at[:, 0].set(jax.random.normal(
        jax.random.PRNGKey(1), (1, cfg.d_model)).astype(jnp.bfloat16))
    y1 = attention(x1, p, cfg, causal=True)
    y2 = attention(x2, p, cfg, causal=True)
    np.testing.assert_allclose(np.array(y1[:, 4:], np.float32),
                               np.array(y2[:, 4:], np.float32), atol=1e-6)


def test_attention_chunk_invariance():
    import dataclasses
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    specs = attention_param_specs(cfg, layers=0)
    p = init_param_tree(KEY, specs)
    x = jax.random.normal(KEY, (2, 32, cfg.d_model)).astype(jnp.bfloat16)
    outs = []
    for ch in (8, 16, 32):
        c2 = dataclasses.replace(cfg, attn_chunk=ch)
        outs.append(np.array(attention(x, p, c2, causal=True), np.float32))
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-5)
    np.testing.assert_allclose(outs[0], outs[2], atol=1e-5)
