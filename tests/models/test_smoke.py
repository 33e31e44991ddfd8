"""Per-architecture smoke tests: reduced configs, one forward/train step on
CPU, asserting output shapes and no NaNs (assignment requirement)."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, get_config
from repro.configs.base import ShapeConfig
from repro.models import model_api, param_count
from repro.models.shardlib import init_param_tree


KEY = jax.random.PRNGKey(0)


def _batch(cfg, b=2, s=64):
    batch = {"tokens": jnp.full((b, s), 3, jnp.int32),
             "labels": jnp.ones((b, s), jnp.int32)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.full(
            (b, cfg.frontend_tokens, cfg.d_model), 0.01, jnp.bfloat16)
    if cfg.family == "encdec":
        batch["frames"] = jnp.full(
            (b, s // cfg.enc_frames_ratio, cfg.d_model), 0.01, jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_train_step_smoke(arch):
    cfg = get_config(arch, smoke=True)
    api = model_api(cfg)
    params = api.init_params(KEY)
    batch = _batch(cfg)

    def train(p, b):
        loss, grads = jax.value_and_grad(api.loss)(p, b)
        return loss, jax.tree.map(lambda x, g: x - 1e-3 * g.astype(x.dtype),
                                  p, grads)

    loss, new_params = jax.jit(train)(params, batch)
    assert loss.shape == ()
    assert jnp.isfinite(loss)
    for leaf, old in zip(jax.tree.leaves(new_params), jax.tree.leaves(params)):
        assert leaf.shape == old.shape and leaf.dtype == old.dtype
        assert jnp.isfinite(leaf.astype(jnp.float32)).all()


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_decode_step_smoke(arch):
    cfg = get_config(arch, smoke=True)
    api = model_api(cfg)
    params = api.init_params(KEY)
    shape = ShapeConfig("t", 32, 2, "decode")
    state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         api.decode_state_specs(shape),
                         is_leaf=lambda x: hasattr(x, "struct"))
    step = jax.jit(api.decode_step)
    logits, state = step(params, state, jnp.full((2, 1), 5, jnp.int32))
    assert logits.shape == (2, cfg.padded_vocab)
    assert jnp.isfinite(logits).all()
    logits2, state2 = step(params, state, jnp.full((2, 1), 7, jnp.int32))
    assert jnp.isfinite(logits2).all()
    assert state2["index"].shape == (2,)          # per-row (slot) positions
    assert (state2["index"] == 2).all()
    assert not jnp.allclose(logits, logits2)      # cache actually advanced


@pytest.mark.parametrize("arch", ["qwen1.5-110b", "grok-1-314b",
                                  "llava-next-mistral-7b",
                                  "seamless-m4t-medium"])
def test_prefill_smoke(arch):
    cfg = get_config(arch, smoke=True)
    api = model_api(cfg)
    params = api.init_params(KEY)
    batch = {k: v for k, v in _batch(cfg, s=16).items() if k != "labels"}
    logits, state = jax.jit(lambda p, b: api.prefill(p, b, max_len=32))(
        params, batch)
    assert logits.shape == (2, cfg.padded_vocab)
    assert jnp.isfinite(logits).all()
    expect = 16 + (cfg.frontend_tokens if cfg.family == "vlm" else 0)
    assert state["index"].shape == (2,)           # per-row (slot) positions
    assert (state["index"] == expect).all()
    # continue decoding from the prefilled state
    lg, state = jax.jit(api.decode_step)(params, state,
                                         jnp.full((2, 1), 5, jnp.int32))
    assert jnp.isfinite(lg).all()


EXPECTED_PARAMS_B = {
    "llava-next-mistral-7b": 7.11, "grok-1-314b": 315.7,
    "llama4-scout-17b-a16e": 106.7, "granite-20b": 20.0,
    "qwen1.5-110b": 110.0, "starcoder2-3b": 3.03, "phi4-mini-3.8b": 3.84,
    "seamless-m4t-medium": 0.72, "zamba2-2.7b": 2.35, "rwkv6-1.6b": 1.45,
    "granite-4.0-h-small": 32.2, "granite-4.0-h-small-share": 4.418,
}


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_full_config_param_count(arch):
    """Full (not smoke) configs carry the assigned dimensions: their param
    counts must match the architecture names."""
    n = param_count(model_api(get_config(arch)).param_specs()) / 1e9
    assert n == pytest.approx(EXPECTED_PARAMS_B[arch], rel=0.02)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_exact_assigned_dimensions(arch):
    cfg = get_config(arch)
    spec = {
        "llava-next-mistral-7b": (32, 4096, 32, 8, 14336, 32000),
        "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "granite-20b": (52, 6144, 48, 1, 24576, 49152),
        "qwen1.5-110b": (80, 8192, 64, 8, 49152, 152064),
        "starcoder2-3b": (30, 3072, 24, 2, 12288, 49152),
        "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200064),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "rwkv6-1.6b": (24, 2048, 32, 32, 7168, 65536),
        "granite-4.0-h-small": (40, 4096, 32, 8, 768, 100352),
        "granite-4.0-h-small-share": (20, 4096, 32, 8, 768, 100352),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
           cfg.vocab_size)
    assert got == spec
    if arch == "qwen1.5-110b":
        assert cfg.qkv_bias
    if arch == "llava-next-mistral-7b":
        assert cfg.sliding_window == 4096
    if arch == "grok-1-314b":
        assert (cfg.n_experts, cfg.top_k) == (8, 2)
    if arch == "llama4-scout-17b-a16e":
        assert (cfg.n_experts, cfg.top_k, cfg.shared_expert) == (16, 1, True)
    if arch == "zamba2-2.7b":
        assert cfg.ssm_state == 64 and cfg.shared_attn_period == 6
    if arch.startswith("granite-4.0-h-small"):
        mamba, attn = ("mamba2", "experts"), ("attention", "experts")
        period = (mamba,) * 5 + (attn,) + (mamba,) * 4
        assert (cfg.n_experts, cfg.top_k, cfg.layer_types) == (
            72, 10, period * (cfg.n_layers // 10))
        assert (cfg.d_expert, cfg.d_shared_expert, cfg.ssm_state,
                cfg.ssm_d_head, cfg.ssm_conv_bias) == (768, 1536, 128, 64, True)
        assert (cfg.embedding_multiplier, cfg.residual_multiplier,
                cfg.attention_multiplier, cfg.logits_scaling,
                cfg.rms_norm_eps, cfg.position_embedding_type) == (
            12, 0.22, 1 / 128, 16, 1e-5, "nope")
        assert cfg.n_experts_held == (9 if arch.endswith("share") else 72)
