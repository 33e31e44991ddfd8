"""The engine's expert-routing counters at smoke size on the CPU: after a
short run, ``moe_pairs_total`` and ``moe_experts_hit_total`` equal a count
recomputed from the plain reference's router over the absorbed prompts'
tokens, and batched steps count nothing; models without experts register
neither counter.

The program's hidden states are bf16 and the reference's float32, so
where a token's ``top_k``-th and next logits lie closer than ``TIE``
and one of the two experts is held, the two may route it differently: the
counts may differ by at most the number of such near ties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.refs import common
from bench.tests import smoke
from repro.configs import get_config
from repro.models import model_api
from repro.serve import Request, ServeEngine

COUNTERS = ("moe_pairs_total", "moe_experts_hit_total")
#: Router logits closer than this are a near tie at the program's bf16.
TIE = 0.01


def _reference_routes(ref, params, model, tokens):
    """Top-k expert ids of every expert block (layers, positions, k), and
    where the top-k-th and next logit are a near tie across the held
    boundary (layers, positions): the reference's layers, one by one, over
    the whole sequence."""
    x = params["embedding"][jnp.asarray(tokens)].astype(jnp.float32) \
        * model["embedding_multiplier"]
    k, held = model["top_k"], model["experts_held"]
    seen = dict.fromkeys(ref.STACK, 0)
    routes, ties = [], []
    for kinds in model["layer_types"]:
        for kind in kinds:
            lp = jax.tree.map(lambda a: a[seen[kind]], params[ref.STACK[kind]])
            seen[kind] += 1
            h = common.rmsnorm(x, lp["norm"], model["rms_norm_eps"])
            if kind == "experts":
                top, idx = jax.lax.top_k(common.mm_f32(h, lp["router"]), k + 1)
                top, idx = np.asarray(top), np.asarray(idx)
                routes.append(idx[:, :k])
                ties.append((top[:, k - 1] - top[:, k] < TIE)
                            & ((idx[:, k - 1] < held) != (idx[:, k] < held)))
            x = x + model["residual_multiplier"] * ref.BLOCKS[kind](
                h, lp, model, common.mm_f32)
    return np.stack(routes), np.stack(ties)


def test_counters_match_the_reference_router():
    """Two requests admitted together: both prompts absorbed, then batched
    steps, which carry no count in their state and add none."""
    conf = smoke.config("granite-4.0-h-small")
    model = conf["model"]
    ref, params = harness.make_weights(conf, 5)
    engine = ServeEngine(harness.program_config(conf), params, slots=2,
                         max_len=64)
    rng = np.random.default_rng(5)
    reqs = [Request(uid=0, prompt=rng.integers(3, 512, 9).tolist(),
                    max_new_tokens=7),
            Request(uid=1, prompt=rng.integers(3, 512, 6).tolist(),
                    max_new_tokens=3)]
    for r in reqs:
        engine.submit(r)
    assert engine.run_until_drained().completed == 2
    assert engine.stats.decode_steps > 0
    assert "routed" not in engine._state
    held = model["experts_held"]
    want, ties = 0, 0
    for r in reqs:
        routes, tie = _reference_routes(ref, params, model,
                                        r.prompt + r.out_tokens[:-1])
        n = len(r.prompt)
        # one token a step: every pair is a held expert hit
        want += int((routes[:, :n] < held).sum())
        ties += int(tie[:, :n].sum())
    reg = engine.obs.registry
    got = [reg.counter(c).value(step="absorb") for c in COUNTERS]
    assert [reg.counter(c).value(step="batched") for c in COUNTERS] == [0, 0]
    assert want > 0 and ties <= 3, (want, ties)
    for g in got:
        assert abs(g - want) <= ties, (got, want, ties)


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "zamba2-2.7b"])
def test_models_without_experts_register_no_counter(arch):
    cfg = get_config(arch, smoke=True)
    engine = ServeEngine(cfg, model_api(cfg).init_params(
        jax.random.PRNGKey(0)), slots=2, max_len=32)
    engine.submit(Request(uid=0, prompt=[5, 6, 7], max_new_tokens=3))
    engine.run_until_drained()
    assert not set(COUNTERS) & set(engine.obs.registry.names())
