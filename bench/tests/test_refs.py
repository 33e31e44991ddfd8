"""The plain references against the program, at smoke size on the CPU:
prefill (or per-token absorption) then cached decode agrees with the
float32 reference on logits, and the int8 control fails the same
tolerance; served tokens pass the check's limits and the control's fail
them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.refs import common
from bench.tests import smoke

#: Relative RMS of the logits against the reference, per configuration:
#: above the program's readings (seeds 0-2: at most 0.011 phi4, 0.029
#: zamba2; bf16 weights and activations) and below the int8 control's
#: (at least 0.034 and 0.066).
LOGIT_RTOL = {"phi4-mini-3.8b": 0.02, "zamba2-2.7b": 0.045}
MAX_LEN = 128


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")


def _rel(a, b):
    return float(np.sqrt(np.mean((a - b) ** 2)) / np.sqrt(np.mean(b ** 2)))


def _program_logits(conf, params, prompt, tokens):
    """Logits of the program's own path: prefill (dense) or one decode step
    per prompt token (hybrid), then cached decode of ``tokens[:-1]``."""
    from repro.configs.base import ShapeConfig
    from repro.models import model_api
    api = model_api(harness.program_config(conf))
    step = jax.jit(api.decode_step)
    if conf["model"]["family"] == "dense":
        lg, st = jax.jit(api.prefill, static_argnames=("max_len",))(
            params, {"tokens": jnp.asarray([prompt])}, max_len=MAX_LEN)
    else:
        st = api.make_decode_state(ShapeConfig("s", MAX_LEN, 1, "decode"))
        for t in prompt:
            lg, st = step(params, st, jnp.asarray([[t]]))
    out = [np.asarray(lg)[0]]
    for t in tokens[:-1]:
        lg, st = step(params, st, jnp.asarray([[t]]))
        out.append(np.asarray(lg)[0])
    return np.stack(out)


def _ref_logits(ref, conf, params, prompt, tokens, mm):
    seq = jnp.asarray(prompt + tokens[:-1])
    pos = jnp.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    h = ref.hidden(params, conf["model"], seq, mm)[pos]
    return np.asarray(mm(h, params["embedding"].T))


@pytest.mark.parametrize("name", sorted(smoke.SIZES))
@pytest.mark.parametrize("seed", [0, 7])
def test_cached_decode_agrees_with_the_reference(name, seed):
    conf = smoke.config(name)
    ref, params = harness.make_weights(conf, seed)
    rng = np.random.default_rng(seed)
    prompt = rng.integers(3, 512, 24).tolist()
    tokens = rng.integers(3, 512, 12).tolist()
    prog = _program_logits(conf, params, prompt, tokens)
    want = _ref_logits(ref, conf, params, prompt, tokens, common.mm_f32)
    ctrl = _ref_logits(ref, conf, params, prompt, tokens, common.mm_int8)
    assert _rel(prog, want) <= LOGIT_RTOL[name]
    assert _rel(ctrl, want) > LOGIT_RTOL[name]


def _served(conf, params, seed):
    """Eight requests of mixed lengths through ``ServeEngine`` on four
    slots, so that several slots decode at once and admissions land in a
    live batch."""
    from repro.serve import Request
    engine, _ = harness.build_engine(conf, params, annotate=False)
    rng = np.random.default_rng(seed)
    reqs = [Request(uid=i, prompt=rng.integers(3, 512, int(n)).tolist(),
                    max_new_tokens=int(m))
            for i, (n, m) in enumerate(zip(rng.integers(4, 40, 8),
                                           rng.integers(8, 40, 8)))]
    for r in reqs:
        engine.submit(r)
    stats = engine.run_until_drained()
    assert stats.completed == len(reqs)
    return [{"prompt": r.prompt, "tokens": r.out_tokens} for r in reqs]


@pytest.mark.parametrize("name", sorted(smoke.SIZES))
@pytest.mark.parametrize("seed", [0, 1])
def test_served_tokens_pass_and_the_control_fails(name, seed):
    conf = smoke.config(name)
    ref, params = harness.make_weights(conf, seed)
    got = harness.gap_readings(ref, params, conf["model"],
                               _served(conf, params, seed), control=True)
    limits = conf["limits"]
    assert all(got[k] <= v for k, v in limits.items()), got
    assert any(got["control_" + k] > v for k, v in limits.items()), got
    assert got["control_mean_gap"] > limits["mean_gap"]


def test_int8_product_is_exact_on_integers():
    x = jnp.asarray([[1.0, -2.0, 127.0]])
    w = jnp.asarray([[1.0], [2.0], [-127.0]])
    assert float(common.mm_int8(x, w)[0, 0]) == pytest.approx(
        float(common.mm_f32(x, w)[0, 0]), rel=1e-6)


def test_served_gaps_pad_to_few_shapes():
    assert common.bucket(1, 512) == 512
    assert common.bucket(513, 512) == 1024
    assert common.bucket(0, 256) == 256
