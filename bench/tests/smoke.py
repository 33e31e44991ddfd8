"""A checkout in miniature for the tests, built from the benchmark's own
files alone: every configuration of ``BENCHMARK.json`` at the smoke sizes
of its program arch (``repro.configs``' ``smoke_config``) with the file's
``smoke_limits`` as its limits, every mix with its ``smoke`` block in
place of the keys it names, the same cells, the same references."""

from __future__ import annotations

import dataclasses
import json
import shutil
from pathlib import Path
from typing import Dict, List

from bench import harness

ROOT = harness.ROOT
#: The engine every smoke configuration runs.
ENGINE = {"slots": 4, "max_len": 128}


def benchmark() -> Dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def config_names() -> List[str]:
    return [c["name"] for c in benchmark()["configs"]]


def cells() -> List[str]:
    return [w["name"] for w in benchmark()["workloads"]]


def smoke_model(conf: Dict) -> Dict:
    """The file's model with every size in which its arch's smoke config
    differs from the published one."""
    from repro.configs import get_config
    full = get_config(conf["arch"])
    small = get_config(conf["arch"], smoke=True)
    return {**conf["model"],
            **{f.name: getattr(small, f.name)
               for f in dataclasses.fields(small)
               if getattr(small, f.name) != getattr(full, f.name)}}


def config(name: str) -> Dict:
    entry = {c["name"]: c for c in benchmark()["configs"]}[name]
    conf = json.loads((ROOT / entry["file"]).read_text())
    conf["model"] = smoke_model(conf)
    conf["engine"].update(ENGINE)
    conf["limits"] = dict(conf["smoke_limits"])
    return conf


def mix(name: str) -> Dict:
    raw = json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())
    small = raw.pop("smoke")
    return {**raw, **small}


def off_chip(monkeypatch) -> None:
    """Let a whole run go on the CPU: no chip check, no persistent compile
    cache, no peaks (the roofline and MFU readers stay silent)."""
    import jax
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices()[0])
    monkeypatch.setattr(harness, "peaks", lambda kind: None)


def make_root(tmp: Path) -> Path:
    """``tmp`` laid out as a checkout: BENCHMARK.json, and each
    configuration, reference and mix file that it names, at smoke size."""
    bench = benchmark()
    for d in ("configs", "refs", "traffic"):
        (tmp / "bench" / d).mkdir(parents=True)
    for c in bench["configs"]:
        (tmp / c["file"]).write_text(json.dumps(config(c["name"])))
        shutil.copy(ROOT / "bench" / "refs" / f"{c['name']}.py",
                    tmp / "bench" / "refs")
    for w in bench["workloads"]:
        (tmp / "bench" / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(mix(w["traffic"])))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
