"""Traffic from a mix file: ``bench/traffic/<mix>.json``.

One generator reads every mix.  A mix file holds only parameters::

    {"arrivals": "poisson_open" | "gamma_open" | "closed_loop",
     "rate_rps": 1.6,                  # open loops: mean arrival rate
     "gamma_shape": 0.25,              # gamma_open: shape k (CV = 1/sqrt(k))
     "clients": 32, "think_s": 0.0,    # closed_loop
     "prompt": {"median": 512, "sigma": 0.8, "min": 256, "max": 1536,
                "round_to": 256},      # clipped lognormal, rounded up
     "output": {"median": 160, "sigma": 0.7, "min": 16, "max": 480},
     "smoke": {...}}                   # top-level keys the tests' smoke
                                       # size replaces; ignored here

Every seed gets the same work in the same order: lengths and inter-arrival
gaps are the quantiles ``(i + 0.5) / n`` of their distributions (a
stratified sample), shuffled and paired (prompt with output) once, by
``ORDER_SEED``, alike for every run.  The seed draws only the prompts'
token ids, which change no size and no time, so two seeds queue alike.  An
open loop makes ``round(rate * seconds)`` arrivals, all due inside the
window; a closed loop draws blocks of ``clients`` requests, each block a
full stratified set.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List, Sequence

import numpy as np
from scipy.special import gammaincinv

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
ARRIVALS = ("poisson_open", "gamma_open", "closed_loop")
#: Seed of the one order of gaps and pairing of lengths that every run
#: serves; ``--seed`` never changes it.
ORDER_SEED = 0


@dataclasses.dataclass(frozen=True)
class Item:
    """One request of a schedule: due ``due_s`` after the window opens
    (open loops; 0 in a closed loop), its prompt and output length."""
    due_s: float
    prompt: List[int]
    max_new_tokens: int


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> Dict:
    mix = json.loads((Path(directory) / f"{name}.json").read_text())
    mix.pop("smoke", None)
    if mix.get("arrivals") not in ARRIVALS:
        raise ValueError(f"mix {name}: arrivals must be one of {ARRIVALS}")
    return mix


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` stratified lengths of a clipped lognormal with ``median`` and
    ``sigma``, rounded up to ``round_to`` and kept inside [min, max]."""
    z = np.array([NormalDist().inv_cdf(u) for u in quantiles(n)])
    raw = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    out = np.clip(raw, spec["min"], spec["max"]).astype(np.int64)
    step = int(spec.get("round_to", 1))
    if step > 1:
        out = np.minimum(-(-out // step) * step, spec["max"])
    return out


def gaps(mix: Dict, n: int) -> np.ndarray:
    """``n`` stratified inter-arrival gaps of mean ``1 / rate_rps``."""
    u = quantiles(n)
    if mix["arrivals"] == "poisson_open":
        g = -np.log1p(-u)
    elif mix["arrivals"] == "gamma_open":
        k = float(mix["gamma_shape"])
        g = gammaincinv(k, u) / k
    else:
        raise ValueError("closed loops have no arrival schedule")
    return g / g.mean() / float(mix["rate_rps"])


def _prompts(rng: np.random.Generator, lens: Sequence[int],
             vocab: int) -> List[List[int]]:
    return [rng.integers(3, vocab, int(n)).tolist() for n in lens]


def open_schedule(mix: Dict, seed: int, seconds: float,
                  vocab: int) -> List[Item]:
    """Every arrival due in ``[0, seconds)``: ``round(rate * seconds)`` of
    them, the first at 0, each next one a shuffled stratified gap later,
    the gaps scaled so that all ``n`` of them span the window (the last
    gap runs past the close).  Only the token ids depend on ``seed``."""
    n = max(1, int(round(float(mix["rate_rps"]) * seconds)))
    order = np.random.default_rng(ORDER_SEED)
    g = order.permutation(gaps(mix, n)) * (seconds / (n / float(mix["rate_rps"])))
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    plen = order.permutation(lengths(mix["prompt"], n))
    olen = order.permutation(lengths(mix["output"], n))
    prompts = _prompts(np.random.default_rng(seed), plen, vocab)
    return [Item(float(t), p, int(o)) for t, p, o in zip(due, prompts, olen)]


def closed_pool(mix: Dict, seed: int, blocks: int, vocab: int) -> List[Item]:
    """``blocks`` blocks of ``clients`` requests, each block a shuffled
    stratified set of prompt and output lengths.  Only the token ids depend
    on ``seed``."""
    c = int(mix["clients"])
    order = np.random.default_rng(ORDER_SEED)
    rng = np.random.default_rng(seed)
    out: List[Item] = []
    for _ in range(blocks):
        plen = order.permutation(lengths(mix["prompt"], c))
        olen = order.permutation(lengths(mix["output"], c))
        out += [Item(0.0, p, int(o)) for p, o in
                zip(_prompts(rng, plen, vocab), olen)]
    return out


def prompt_lengths(mix: Dict) -> List[int]:
    """Every prompt length the mix can produce (the shapes to warm up)."""
    spec = mix["prompt"]
    step = int(spec.get("round_to", 1))
    if step <= 1:
        return []
    lo = -(-int(spec["min"]) // step) * step
    return list(range(lo, int(spec["max"]) + 1, step))


