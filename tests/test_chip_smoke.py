"""``chip_smoke.py`` on the CPU: its serving phase at smoke size (control
flow only; speed and full width are the chip's), and its refusal to run
without a TPU."""

import importlib.util
from pathlib import Path

import pytest

from repro.configs import get_config

_ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               _ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


def test_serving_phase_at_smoke_size():
    cfg = get_config(chip_smoke.ARCH, smoke=True)
    max_len, max_new, lens = 64, 4, (8, 16)
    api, engine = chip_smoke.build_engine(cfg, seed=0, slots=2,
                                          max_len=max_len)
    chip_smoke.warm_up(engine, lens, cfg.vocab_size, seed=0)
    compiled_steps = engine.stats.model_steps
    prompts = chip_smoke.make_prompts(0, cfg.vocab_size, lens, 5)
    results = chip_smoke.serve(engine, prompts, max_new)
    summary = chip_smoke.check_streams(results, max_new, cfg.vocab_size)
    assert summary["requests_completed"] == 5
    assert summary["tokens_generated"] == 5 * max_new
    assert summary["mean_ttft_s"] > 0
    assert engine.stats.model_steps > compiled_steps
    ref = chip_smoke.check_decode_against_prefill(
        api, engine.params, prompts[0], results[0].tokens, max_len)
    assert ref["logit_rel_rms"] <= chip_smoke.LOGIT_RTOL


def test_check_streams_rejects_short_or_foreign_tokens():
    from repro.server.client import GenerateResult

    def result(tokens, status="completed"):
        return GenerateResult(http_status=200, tokens=tokens,
                              summary={"status": status, "ttft_s": 0.1})

    with pytest.raises(chip_smoke.SmokeError, match="of 3 tokens"):
        chip_smoke.check_streams([result([1, 2])], 3, 10)
    with pytest.raises(chip_smoke.SmokeError, match="outside the vocab"):
        chip_smoke.check_streams([result([1, 2, 10])], 3, 10)
    with pytest.raises(chip_smoke.SmokeError, match="truncated"):
        chip_smoke.check_streams([result([1, 2, 3], "truncated")], 3, 10)


def test_main_exits_nonzero_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "needs a TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""
