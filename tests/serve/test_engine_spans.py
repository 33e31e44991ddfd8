"""The engine's step as a tree of spans, for a dense model (one prefill
program per prompt) and a hybrid one (prompts absorbed one batch-1 step
per token): every ``prefill`` and ``decode_step`` lies in its
``engine_step``, each child inside its parent, ``live`` counts the slots
already decoding, a prompt of a new length compiles nothing on the
hybrid, and tracing changes no served token."""

import jax
import pytest

from repro.configs import get_config
from repro.models import model_api
from repro.obs import ObsBus, compiles
from repro.serve import Request, ServeEngine

CHILDREN = {"engine_step": {"prefill", "decode_step"},
            "prefill": set(),
            "decode_step": {"device_wait"}}


@pytest.fixture(scope="module", params=["starcoder2-3b", "zamba2-2.7b"])
def model(request):
    cfg = get_config(request.param, smoke=True)
    return cfg, model_api(cfg).init_params(jax.random.PRNGKey(0))


def _serve(cfg, params, enabled=True):
    """Request 0 decodes alone for two steps, then request 1 is admitted
    beside it (one live slot), then both drain."""
    bus, out = ObsBus(enabled=enabled), []
    bus.tracer.add_sink(out.append)
    eng = ServeEngine(cfg, params, slots=2, max_len=32, obs=bus)
    reqs = [Request(uid=0, prompt=[5, 6, 7], max_new_tokens=6),
            Request(uid=1, prompt=[9, 8, 7, 6, 5], max_new_tokens=3)]
    eng.submit(reqs[0])
    eng.step()
    eng.step()
    eng.submit(reqs[1])
    eng.run_until_drained()
    return eng, out, [r.out_tokens for r in reqs]


def test_every_step_is_a_tree_of_nested_spans(model):
    cfg, params = model
    eng, out, _ = _serve(cfg, params)
    spans = [e for e in out if e["kind"] == "span"]
    by_id = {e["id"]: e for e in spans}
    assert len(by_id) == len(spans)
    for e in spans:
        if e["name"] == "engine_step":
            assert e["parent"] is None
            continue
        parent = by_id[e["parent"]]
        assert e["name"] in CHILDREN[parent["name"]]
        assert parent["t"] <= e["t"]
        assert e["t"] + e["dur_s"] <= parent["t"] + parent["dur_s"]
    for name, kids in CHILDREN.items():
        for p in (e for e in spans if e["name"] == name):
            got = {e["name"] for e in spans if e["parent"] == p["id"]}
            assert got <= kids
            if name == "decode_step":
                assert got == kids
    steps = [e for e in spans if e["name"] == "engine_step"]
    assert len(steps) == sum(1 for e in spans if e["name"] == "decode_step")
    assert sum(1 for e in spans if e["name"] == "prefill") == 2
    assert eng.obs.tracer._open_spans() == []


def test_prefill_counts_live_slots_and_calls(model):
    cfg, params = model
    eng, out, _ = _serve(cfg, params)
    pre = {e["uid"]: e for e in out if e["name"] == "prefill"}
    assert pre[0]["live"] == 0
    assert pre[1]["live"] == 1           # request 0 already decoding
    per = (lambda p: p["prompt_len"]) if cfg.family in ("ssm", "hybrid") \
        else (lambda p: 1)
    assert eng.stats.prefill_steps == sum(per(p) for p in pre.values())


def test_compiles_are_attributed_to_the_engine_step(model):
    cfg, params = model
    _, out, _ = _serve(cfg, params)
    steps = [e for e in out if e["name"] == "engine_step"]
    comp = [e for e in out if e["name"] == "jit_compile"]
    assert any(e["fn"] == "jit(decode_step)" for e in comp)
    for c in comp:
        assert any(s["t"] <= c["t"] <= s["t"] + s["dur_s"] for s in steps)
    # one prefill program per prompt length (3 and 5)
    if cfg.family not in ("ssm", "hybrid"):
        assert sum(e["fn"] == "jit(prefill)" for e in comp) == 2


def test_hybrid_prompt_of_new_length_compiles_nothing():
    """Prompts absorbed one token per step lower no program per length."""
    cfg = get_config("zamba2-2.7b", smoke=True)
    params = model_api(cfg).init_params(jax.random.PRNGKey(0))
    bus, out = ObsBus(), []
    bus.tracer.add_sink(out.append)
    eng = ServeEngine(cfg, params, slots=2, max_len=32, obs=bus)
    eng.submit(Request(uid=0, prompt=[5, 6, 7], max_new_tokens=2))
    eng.run_until_drained()
    del out[:]
    for uid, n in ((1, 23), (2, 29)):    # lengths no other test serves
        eng.submit(Request(uid=uid, prompt=[4] * n, max_new_tokens=2))
    eng.run_until_drained()
    assert eng.stats.completed == 3
    assert [e["fn"] for e in out if e["name"] == "jit_compile"] == []


def test_served_tokens_bit_identical_with_tracing_off(model):
    cfg, params = model
    _, _, on = _serve(cfg, params)
    eng, out, off = _serve(cfg, params, enabled=False)
    assert on == off
    assert [len(t) for t in on] == [6, 3]
    assert out == [] and len(eng.obs.recorder) == 0


def test_listener_installed_once_however_many_engines():
    from jax._src import monitoring
    cfg = get_config("starcoder2-3b", smoke=True)
    params = model_api(cfg).init_params(jax.random.PRNGKey(0))
    for _ in range(2):
        ServeEngine(cfg, params, slots=1, max_len=16)
    assert compiles.install() is False
    assert monitoring.get_event_duration_listeners().count(
        compiles._on_duration) == 1
