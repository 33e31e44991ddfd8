"""A checkout in miniature for the tests: the benchmark's own files with
each configuration cut to the program's smoke sizes and small mixes."""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

from bench.harness import ROOT

#: Smoke sizes per configuration (those of ``repro.configs.*.smoke_config``).
SIZES = {
    "phi4-mini-3.8b": dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                           d_head=32, d_ff=256, vocab_size=512, attn_chunk=32),
    "zamba2-2.7b": dict(n_layers=4, d_model=128, n_heads=4, n_kv_heads=4,
                        d_head=32, d_ff=256, vocab_size=512, ssm_state=16,
                        ssm_d_head=16, ssm_chunk=8, shared_attn_period=2,
                        attn_chunk=32),
}
#: Limits at smoke size, between the program's readings and the int8
#: control's on the CPU (seeds 0-3: program widest gap at most 0.0054
#: (phi4) and 0.021 (zamba2), mean gap 6.6e-5 and 2.3e-4; control widest
#: gap at least 0.013 and 0.056, mean gap 3.2e-4 and 1.6e-3).
LIMITS = {"phi4-mini-3.8b": {"logit_gap": 0.009, "mean_gap": 1.5e-4},
          "zamba2-2.7b": {"logit_gap": 0.035, "mean_gap": 6e-4}}
MIXES = {
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


def config(name: str) -> Dict:
    conf = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    conf["model"].update(SIZES[name])
    conf["engine"].update(slots=4, max_len=128)
    conf["limits"] = dict(LIMITS[name])
    return conf


def make_root(tmp: Path) -> Path:
    """``tmp`` laid out as a checkout: BENCHMARK.json, configuration and
    mix files at smoke size, the same cell names."""
    small = json.loads((ROOT / "BENCHMARK.json").read_text())
    small["configs"] = [{"name": n, "file": f"bench/configs/{n}.json"}
                        for n in SIZES]
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir(parents=True)
    for c in small["configs"]:
        (tmp / c["file"]).write_text(json.dumps(config(c["name"])))
    for w in small["workloads"]:
        (tmp / "bench" / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(MIXES[w["traffic"]]))
    (tmp / "BENCHMARK.json").write_text(json.dumps(small))
    return tmp
