"""Whole runs at smoke size on the CPU, the chip check skipped: a sound
run comes out correct with every metric of its cell, a run with the
timed path broken underneath comes out not correct, once per fault a
served cell can have, and so does a run that judges the int8 control's
tokens in place of the served ones."""

import pytest

from bench import harness
from bench.tests import smoke

SECONDS = 2.0


@pytest.fixture
def root(tmp_path, monkeypatch):
    import jax
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices()[0])
    monkeypatch.setattr(harness, "peaks", lambda kind: None)
    return smoke.make_root(tmp_path)


def _run(root, cell, trace=False):
    return harness.run(cell, 2 ** 33 + 11, SECONDS, trace, root=root,
                       log=lambda m: None)


@pytest.mark.parametrize("cell", ["phi4.chat", "zamba2.chat_closed"])
def test_sound_run_is_correct(root, cell):
    out = _run(root, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in harness.find_cell(
        harness.load_benchmark(root), cell, root).end_to_end}
    assert set(out["metrics"]) == want
    assert list(out)[-1] == "checks"
    assert out["checks"]["logit_gap"]["value"] <= \
        out["checks"]["logit_gap"]["limit"]


def test_traced_run_reports_its_layers(root):
    out = _run(root, "phi4.code_burst", trace=True)
    assert out["correct"]
    # the CPU has no peaks: the roofline and MFU readers stay silent
    assert {"decode_step_ms", "prefill_ms_per_ktok"} <= set(out["metrics"])
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert len(out["breakdown"]["idle_gaps"]) <= 10


def _alter_token(monkeypatch):
    """A token altered where it is produced: the engine emits argmax + 1."""
    from repro.serve.engine import ServeEngine
    emit = ServeEngine._emit

    def altered(self, slot, req, tok):
        emit(self, slot, req, (tok + 1) % self.cfg.padded_vocab)

    monkeypatch.setattr(ServeEngine, "_emit", altered)


def _stale_state(monkeypatch):
    """A step that returns its state unchanged: decode keeps the cache and
    recurrent state it was given."""
    from repro.models.api import ModelAPI
    step = ModelAPI.decode_step

    def stale(self, params, state, tokens):
        logits, _ = step(self, params, state, tokens)
        return logits, state

    monkeypatch.setattr(ModelAPI, "decode_step", stale)


@pytest.mark.parametrize("fault", [_alter_token, _stale_state])
@pytest.mark.parametrize("cell", ["phi4.chat", "zamba2.chat_closed"])
def test_broken_timed_path_is_not_correct(root, monkeypatch, cell, fault):
    fault(monkeypatch)
    out = _run(root, cell)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("cell", ["phi4.chat", "zamba2.chat_closed"])
def test_control_is_not_correct(root, monkeypatch, cell):
    monkeypatch.setattr(harness, "compare", harness.control_compare)
    out = _run(root, cell)
    assert not out["correct"]
    assert out["checks"]["mean_gap"]["value"] > \
        out["checks"]["mean_gap"]["limit"]
