"""The smoke root is built from the benchmark's files alone: its sizes,
limits and mixes are those the tests kept by hand before (pinned here),
every cell of ``BENCHMARK.json`` is in it, and a new configuration, mix and
cell join it, and run to ``correct``, as data files and entries alone."""

import json
import shutil

import pytest

from bench import harness, workload
from bench.tests import smoke

#: What the tests kept by hand, by configuration and mix name, before the
#: smoke root was built from the files.
OLD_SIZES = {
    "phi4-mini-3.8b": dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                           d_head=32, d_ff=256, vocab_size=512, attn_chunk=32),
    "zamba2-2.7b": dict(n_layers=4, d_model=128, n_heads=4, n_kv_heads=4,
                        d_head=32, d_ff=256, vocab_size=512, ssm_state=16,
                        ssm_d_head=16, ssm_chunk=8, shared_attn_period=2,
                        attn_chunk=32),
}
OLD_LIMITS = {"phi4-mini-3.8b": {"logit_gap": 0.009, "mean_gap": 1.5e-4},
              "zamba2-2.7b": {"logit_gap": 0.035, "mean_gap": 6e-4}}
OLD_MIXES = {
    "chat": {"arrivals": "poisson_open", "rate_rps": 6.0,
             "prompt": {"median": 16, "sigma": 0.5, "min": 8, "max": 32,
                        "round_to": 8},
             "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16}},
    "code_burst": {"arrivals": "gamma_open", "rate_rps": 6.0,
                   "gamma_shape": 0.25,
                   "prompt": {"median": 16, "sigma": 0.5, "min": 8,
                              "max": 32, "round_to": 8},
                   "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}},
    "chat_closed": {"arrivals": "closed_loop", "clients": 6, "think_s": 0.0,
                    "prompt": {"median": 12, "sigma": 0.5, "min": 4,
                               "max": 24},
                    "output": {"median": 8, "sigma": 0.5, "min": 4,
                               "max": 16}},
}


@pytest.mark.parametrize("name", sorted(OLD_SIZES))
def test_smoke_sizes_are_the_hand_kept_ones(name):
    """The program's smoke config adds only ``loss_chunk``, the training
    loss's chunk, which the served path never reads."""
    conf = smoke.config(name)
    full = json.loads((harness.ROOT / "bench" / "configs" /
                       f"{name}.json").read_text())
    assert conf["model"] == {**full["model"], **OLD_SIZES[name],
                             "loss_chunk": 32}
    assert harness.program_config(conf).loss_chunk == 32
    assert conf["limits"] == OLD_LIMITS[name]
    assert conf["engine"] == {**full["engine"], "slots": 4, "max_len": 128}


@pytest.mark.parametrize("name", sorted(OLD_MIXES))
def test_smoke_mixes_are_the_hand_kept_ones(name):
    got = smoke.mix(name)
    assert {k: v for k, v in got.items() if k != "source"} == OLD_MIXES[name]


def test_smoke_root_holds_every_cell(tmp_path):
    root = smoke.make_root(tmp_path)
    bench = harness.load_benchmark(root)
    assert bench == smoke.benchmark()
    assert [w["name"] for w in bench["workloads"]] == smoke.cells()
    for w in bench["workloads"]:
        cell = harness.find_cell(bench, w["name"], root)
        assert cell.config["engine"]["max_len"] == 128
        assert cell.mix == workload.load_mix(
            w["traffic"], root / "bench" / "traffic")
        assert "smoke" not in cell.mix
        longest = cell.mix["prompt"]["max"] + cell.mix["output"]["max"]
        assert longest <= cell.config["engine"]["max_len"]


def test_a_configuration_and_a_cell_join_as_data_alone(tmp_path,
                                                       monkeypatch):
    """A copy of zamba2's configuration and reference under a new name, a
    new mix and a cell that joins them: files and entries, no code."""
    smoke.off_chip(monkeypatch)
    root = smoke.make_root(tmp_path)
    conf = json.loads((root / "bench/configs/zamba2-2.7b.json").read_text())
    conf["name"] = "zamba2-copy"
    (root / "bench/configs/zamba2-copy.json").write_text(json.dumps(conf))
    shutil.copy(root / "bench/refs/zamba2-2.7b.py",
                root / "bench/refs/zamba2-copy.py")
    (root / "bench/traffic/tiny_closed.json").write_text(json.dumps({
        "arrivals": "closed_loop", "clients": 3, "think_s": 0.0,
        "prompt": {"median": 6, "sigma": 0.5, "min": 3, "max": 12},
        "output": {"median": 10, "sigma": 0.5, "min": 4, "max": 20},
        # the chip's sizes would not fit the smoke engine: never read here
        "smoke": {"clients": 64}}))
    bench = harness.load_benchmark(root)
    bench["configs"].append({
        "name": "zamba2-copy", "source": "https://huggingface.co/Zyphra/"
        "Zamba2-2.7B", "file": "bench/configs/zamba2-copy.json",
        "reduced": [], "why": "a second configuration of one arch"})
    bench["workloads"].append({
        "name": "zamba2copy.tiny", "config": "zamba2-copy",
        "traffic": "tiny_closed", "chips": 1, "why": "three clients"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = harness.run("zamba2copy.tiny", 2 ** 33 + 31, 2.0, False,
                      root=root, log=lambda m: None)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 3 and out["failed"] == 0
    # the end-to-end metrics that list no cells
    assert set(out["metrics"]) == {"itl_p50_s", "setup_s"}
