"""The expert layer of one chip's share (``held_experts``), against the
plain reference of granite-4.0-h-small at smoke size on the CPU: the shares
of every chip add up to the uncut layer, and no token is ever dropped."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.refs import common
from repro.configs import get_config
from repro.models.layers import held_experts, mlp, rmsnorm

CFG = get_config("granite-4.0-h-small-share", smoke=True)
REF = common.load("granite-4.0-h-small")


def _model(held):
    """The reference's model dict of the smoke config holding ``held``."""
    c = dataclasses.replace(CFG, experts_held=held)
    return {**{f.name: getattr(c, f.name) for f in dataclasses.fields(c)},
            "ssm_expand": 2, "ssm_conv": 4, "vocab_pad_multiple": 256}


def _uncut_layer(seed):
    """Layer 0 of the uncut expert stack (every expert held), as the
    reference lays it out and draws it."""
    layout = REF.layout(_model(CFG.n_experts))
    params = common.init_params(layout, jax.random.PRNGKey(seed))
    return jax.tree.map(lambda a: a[0], params["moe"])


def _share(layer, j, k):
    """What chip ``j`` of ``n_experts / k`` holds: experts ``jk .. jk+k-1``
    as its first ``k`` (the router's columns reordered alike) and all the
    rest, the shared expert included."""
    e = CFG.n_experts
    order = np.r_[j * k:(j + 1) * k, 0:j * k, (j + 1) * k:e]
    return {**layer, "router": layer["router"][:, order],
            **{w: layer[w][j * k:(j + 1) * k] for w in ("wg", "w1", "w2")}}


def _tokens(seed, n):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, CFG.d_model))


def _experts(x, layer):
    """The program's expert block over tokens ``x`` (n, d), in float32."""
    return held_experts(x[:, None], layer, CFG, CFG.rms_norm_eps)


def _reference(x, layer, held):
    normed = common.rmsnorm(x, layer["norm"], CFG.rms_norm_eps)
    return REF.experts(normed, layer, _model(held), common.mm_f32)


@pytest.mark.parametrize("seed", [0, 1])
def test_shares_add_up_to_the_uncut_layer(seed):
    """The routed parts of every chip's share, plus the shared expert once,
    are the reference's whole expert block with every expert held."""
    layer, x = _uncut_layer(seed), _tokens(seed, 16)
    k = CFG.n_experts_held
    normed = rmsnorm(x, layer["norm"], CFG.rms_norm_eps)
    shared = mlp(normed, layer["shared"], CFG)
    routed = sum(_experts(x, _share(layer, j, k))[0][:, 0] - shared
                 for j in range(CFG.n_experts // k))
    whole = _reference(x, layer, CFG.n_experts)
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    # a share alone is the reference's share: routes elsewhere add nothing
    np.testing.assert_allclose(
        np.asarray(_experts(x, _share(layer, 0, k))[0][:, 0]),
        np.asarray(_reference(x, layer, k)), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("batch", [1, 16])
def test_no_token_is_dropped(batch):
    """Every token gets every held expert it is routed to, whatever the
    batch: rows computed together equal each row computed alone, and a
    batch of one token repeated (every route on the same experts, past any
    capacity) gives that token's output in every row."""
    layer = _uncut_layer(2)
    held_n = CFG.n_experts_held
    held = _share(layer, 0, held_n)
    x = _tokens(3, 64)
    routed = np.asarray((_experts(x, held)[1] < CFG.n_experts_held).sum(-1))
    assert routed.max() >= 2                  # tokens on several held experts
    x = x[np.argsort(-routed)[:batch]]
    got = np.asarray(_experts(x, held)[0][:, 0])
    alone = np.stack([np.asarray(_reference(x[i:i + 1], layer, held_n))[0]
                      for i in range(batch)])
    np.testing.assert_allclose(got, alone, rtol=1e-5, atol=1e-5)
    same = jnp.broadcast_to(x[:1], x.shape)
    rep = np.asarray(_experts(same, held)[0][:, 0])
    np.testing.assert_allclose(rep, np.broadcast_to(alone[0], rep.shape),
                               rtol=1e-5, atol=1e-5)
