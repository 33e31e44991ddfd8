"""Benchmark harness — one function per paper table/figure + kernel/system
micro-benchmarks.  Prints ``name,us_per_call,derived`` CSV rows (deliverable
d).  ``derived`` carries the benchmark's headline quantity (power reduction,
cluster count, rel-error, ...).

    PYTHONPATH=src python -m benchmarks.run [--only tableII] [--fast]
        [--out-dir DIR] [--json-out PATH] [--min-flow-speedup X]

JSON artifacts (``BENCH_serve.json``, ``BENCH_flow.json``,
``BENCH_hwloop.json``, ``BENCH_traffic.json``, ``BENCH_resilience.json``,
``BENCH_railscale.json``) land in ``--out-dir`` (default: CWD); ``--json-out`` overrides the exact path
when a single ``--only`` scenario is run.  ``--min-flow-speedup`` turns the
``flow`` scenario into a CI gate: exit non-zero unless the vectorized sweep
beats the loop-reference sweep by at least that factor.
``--resilience-gate`` does the same for the ``resilience`` scenario: exit
non-zero unless abft-guarded GEMMs show zero silent escapes and the chaos
campaign is all-green.  ``--obs-overhead-gate PCT`` gates the ``obs``
scenario (``BENCH_obs.json``): exit non-zero unless tracing overhead is
below PCT% and two identical virtual-time runs render bit-identical
metric snapshots.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

# single-core hosts deadlock the pure_callback serving path (see conftest);
# arm the XLA thread-pool workaround before any scenario builds a CPU client
from repro.backend import ensure_host_callback_capacity
from repro.launch.compile_cache import enable_compile_cache

ensure_host_callback_capacity()

#: Output routing for JSON artifacts, set by main() from --out-dir/--json-out.
_OUT: Dict[str, Optional[str]] = {"dir": ".", "json_out": None}

#: The shared BENCH_*.json envelope: every JSON-writing scenario emits these
#: top-level keys with identical semantics, and scenario-specific ``config``
#: blocks reuse the same key names for the same concepts (``arch``,
#: ``requests``, ``slots``, ``max_len``, ``array_n``, ``seed``, ...).
BENCH_SCHEMA_KEYS: Tuple[str, ...] = ("scenario", "elapsed_s", "config")


def validate_bench_payload(payload: Dict) -> None:
    """Assert the shared BENCH_*.json schema (tests/benchmarks pins this)."""
    for key in BENCH_SCHEMA_KEYS:
        if key not in payload:
            raise ValueError(f"BENCH payload missing {key!r}; has "
                             f"{sorted(payload)}")
    if not isinstance(payload["scenario"], str) or not payload["scenario"]:
        raise ValueError(f"scenario must be a non-empty string, got "
                         f"{payload['scenario']!r}")
    elapsed = payload["elapsed_s"]
    if not isinstance(elapsed, (int, float)) or not np.isfinite(elapsed) \
            or elapsed < 0:
        raise ValueError(f"elapsed_s must be a finite non-negative number, "
                         f"got {elapsed!r}")
    if not isinstance(payload["config"], dict):
        raise ValueError(f"config must be a dict, got "
                         f"{type(payload['config']).__name__}")


def bench_payload(scenario: str, elapsed_s: float, config: Dict,
                  **extra) -> Dict:
    """Build (and eagerly validate) a BENCH_*.json payload."""
    payload = {"scenario": scenario, "elapsed_s": float(elapsed_s),
               "config": dict(config), **extra}
    validate_bench_payload(payload)
    return payload


def _json_path(default_name: str) -> str:
    """Where a benchmark's JSON artifact goes (honours --out-dir/--json-out)."""
    if _OUT["json_out"]:
        parent = os.path.dirname(_OUT["json_out"])
        if parent:
            os.makedirs(parent, exist_ok=True)
        return _OUT["json_out"]
    out_dir = _OUT["dir"] or "."
    os.makedirs(out_dir, exist_ok=True)
    return os.path.join(out_dir, default_name)


def _time_us(fn: Callable, repeats: int = 3) -> Tuple[float, object]:
    out = fn()                     # warmup + result
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn()
    dt = (time.perf_counter() - t0) / repeats
    return dt * 1e6, out


def bench_tableII(fast: bool) -> List[Tuple[str, float, str]]:
    """Paper Table II: dynamic power, 3 array sizes x 4 techs, model vs paper."""
    from repro.core import validate_against_table2
    rows = []
    us, table = _time_us(lambda: validate_against_table2())
    worst = max(abs(r["delta_pp"]) for r in table)
    rows.append(("tableII/all15rows", us, f"max|delta|={worst:.2f}pp"))
    for r in table[:3]:
        rows.append((f"tableII/{r['tech']}_{r['array']}x{r['array']}", us / 15,
                     f"model={r['model_reduction_pct']:.2f}%"
                     f"_paper={r['paper_reduction_pct']:.2f}%"))
    return rows


def bench_fig15_16(fast: bool) -> List[Tuple[str, float, str]]:
    """Figs. 15/16: 64x64 variant sweep per tech.

    Variant voltage ranges follow the paper: 0.5-1.2 V for 22/45 nm,
    0.7-1.3 V for 130 nm (its threshold is 0.7 V).  The paper's minimum-power
    variants (2x(32x64){0.5,0.6} resp. {0.7,0.8}) must win.  NOTE: the
    paper's quoted 18/21/39% spreads are inconsistent with its own Table II
    reductions under any single P(V) law; our model is calibrated to Table II
    and reports the spread that calibration implies (EXPERIMENTS.md
    §Paper-validation)."""
    from repro.core import model_for
    v_2245 = {
        "2x(32x64){0.5,0.6}": ([0.5, 0.6], [0.5, 0.5]),
        "4x(32x32){0.5,0.6,0.7,0.8}": ([0.5, 0.6, 0.7, 0.8], None),
        "4x(32x32){0.8,1.0,1.2,1.2}": ([0.8, 1.0, 1.2, 1.2], None),
        "2x(32x64){1.0,1.2}": ([1.0, 1.2], [0.5, 0.5]),
    }
    v_130 = {
        "2x(32x64){0.7,0.8}": ([0.7, 0.8], [0.5, 0.5]),
        "4x(32x32){0.7,0.9,1.1,1.3}": ([0.7, 0.9, 1.1, 1.3], None),
        "4x(32x32){0.8,1.0,1.2,1.3}": ([0.8, 1.0, 1.2, 1.3], None),
        "2x(32x64){1.1,1.3}": ([1.1, 1.3], [0.5, 0.5]),
    }
    out = []
    for tech, variants, paper_best in (
            ("vtr-22nm", v_2245, "2x(32x64){0.5,0.6}"),
            ("vtr-45nm", v_2245, "2x(32x64){0.5,0.6}"),
            ("vtr-130nm", v_130, "2x(32x64){0.7,0.8}")):
        m = model_for(tech)

        def sweep():
            return {k: m.partitioned_mw(64, v, frac)
                    for k, (v, frac) in variants.items()}

        us, powers = _time_us(sweep)
        spread = (max(powers.values()) - min(powers.values())) \
            / max(powers.values())
        best = min(powers, key=powers.get)
        out.append((f"fig15_16/{tech}", us,
                    f"spread={spread:.1%}_best={best}"
                    f"_paperbest_match={best == paper_best}"))
    return out


def bench_clustering(fast: bool) -> List[Tuple[str, float, str]]:
    """Figs. 10-14: the four algorithms on 16x16..64x64 min-slack data."""
    from repro.core import (TimingModel, dbscan, hierarchical, kmeans,
                            meanshift)
    sizes = [16, 32] if fast else [16, 32, 64]
    out = []
    for n in sizes:
        slack = TimingModel(n=n, seed=2021).min_slack_flat()
        spread = slack.max() - slack.min()
        algos = {
            "kmeans": lambda: kmeans(slack, 4, seed=0),
            "hierarchical": lambda: hierarchical(slack, 4),
            "meanshift": lambda: meanshift(slack, bandwidth=0.17 * spread),
            "dbscan": lambda: dbscan(slack, eps=spread / 12,
                                     min_pts=max(4, len(slack) // 64)),
        }
        # hierarchical at 64x64 used to be excluded (the O(n^3) loop oracle
        # takes minutes at 4096 points); the nearest-neighbour-cached
        # vectorized rewrite runs it in ~2 s, so it stays in
        for name, fn in algos.items():
            us, labels = _time_us(fn, repeats=1)
            k = len(set(labels.tolist()) - {-1})
            out.append((f"clustering/{name}_{n}x{n}", us, f"clusters={k}"))
    return out


def bench_cadflow(fast: bool) -> List[Tuple[str, float, str]]:
    """End-to-end flow (Fig. 9) incl. Razor-runtime calibration, via the
    staged repro.flow pipeline."""
    from repro.flow import FlowConfig, run
    out = []
    for tech in ("vivado-28nm", "vtr-22nm"):
        us, rep = _time_us(
            lambda t=tech: run(FlowConfig(array_n=16, tech=t, algo="dbscan",
                                          seed=2021)), repeats=1)
        out.append((f"cadflow/16x16_{tech}", us,
                    f"static={rep.static_reduction_pct:.2f}%"
                    f"_runtime={rep.runtime_reduction_pct:.2f}%"))
    return out


def bench_flow_sweep(fast: bool) -> List[Tuple[str, float, str]]:
    """Multi-scenario sweep with shared artifact-prefix caching: the timing
    stage must run once per tech node regardless of how many clustering
    algorithms ride on it."""
    from repro.flow import FlowConfig, sweep
    techs = ["vivado-28nm", "vtr-22nm"] if fast else \
        ["vivado-28nm", "vtr-22nm", "vtr-45nm", "vtr-130nm"]
    algos = ["kmeans", "dbscan"] if fast else \
        ["kmeans", "hierarchical", "meanshift", "dbscan"]

    def go():
        return sweep({"tech": techs, "algo": algos},
                     FlowConfig(array_n=16, seed=2021))

    us, res = _time_us(go, repeats=1)
    return [("flow_sweep/%dtech_x_%dalgo" % (len(techs), len(algos)), us,
             f"configs={len(res.configs)}"
             f"_timing_runs={res.timing_stage_runs()}"
             f"_best={res.best()['runtime_reduction_pct']:.2f}%")]


def bench_flow(fast: bool) -> List[Tuple[str, float, str]]:
    """Vectorized vs loop-reference CAD-flow sweep (the PR's perf headline).

    Runs the full 4-tech x 4-algorithm 16x16 grid twice: once with the
    vectorized hot paths + content-addressed stage sharing, once with the
    bit-exact loop oracles and seed-era cache topology
    (``impl="reference"``, ``Pipeline(content_cache=False)``, per-run power
    fit).  Verifies the 16 FlowReports are bit-identical, then writes the
    timing comparison to BENCH_flow.json.
    """
    from repro.flow import FlowConfig, Pipeline, sweep
    grid = {"tech": ["vivado-28nm", "vtr-22nm", "vtr-45nm", "vtr-130nm"],
            "algo": ["kmeans", "hierarchical", "meanshift", "dbscan"]}
    base = dict(array_n=16, seed=2021)
    repeats = 1 if fast else 3
    sweep(grid, FlowConfig(**base))                    # warm numpy/caches

    runs: Dict[str, Dict] = {}
    for name, impl, cc in (("vectorized", "vectorized", True),
                           ("reference", "reference", False)):
        best_s, res = float("inf"), None
        for _ in range(repeats):
            t0 = time.perf_counter()
            r = sweep(grid, FlowConfig(impl=impl, **base),
                      pipeline=Pipeline(content_cache=cc))
            dt = time.perf_counter() - t0
            if dt < best_s:
                best_s, res = dt, r
        runs[name] = {
            "wall_s": best_s,
            "per_config_s": [round(s, 6) for s in res.elapsed_s],
            "timing_stage_runs": res.store.runs_of("timing"),
            "cluster_stage_runs": res.store.runs_of("cluster"),
            "result": res,
        }

    rv, rr = runs["vectorized"]["result"], runs["reference"]["result"]
    identical = all(
        np.array_equal(a.labels, b.labels)
        and np.array_equal(a.static_v, b.static_v)
        and np.array_equal(np.asarray(a.runtime_v), np.asarray(b.runtime_v))
        and a.n_partitions == b.n_partitions
        and a.baseline_mw == b.baseline_mw and a.static_mw == b.static_mw
        and a.runtime_mw == b.runtime_mw and a.razor_trials == b.razor_trials
        for a, b in zip(rv.reports, rr.reports))
    speedup = runs["reference"]["wall_s"] / runs["vectorized"]["wall_s"]

    payload = bench_payload(
        "flow",
        runs["vectorized"]["wall_s"] + runs["reference"]["wall_s"],
        {**grid, **base, "repeats": repeats},
        configs=len(rv.configs),
        vectorized={k: v for k, v in runs["vectorized"].items()
                    if k != "result"},
        reference={k: v for k, v in runs["reference"].items()
                   if k != "result"},
        speedup=speedup,
        bit_identical_reports=bool(identical),
        best_runtime_reduction_pct=rv.best()["runtime_reduction_pct"],
        notes="reference = loop clustering/simulator/power-fit oracles "
              "with prefix-only caching (seed behaviour); vectorized = "
              "array hot paths + content-addressed cluster/floorplan "
              "sharing. Reports are bit-identical across the two.",
    )
    with open(_json_path("BENCH_flow.json"), "w") as f:
        json.dump(payload, f, indent=2)
    return [
        ("flow/vectorized_4tech_x_4algo_16x16",
         runs["vectorized"]["wall_s"] * 1e6,
         f"cluster_runs={runs['vectorized']['cluster_stage_runs']}"),
        ("flow/reference_4tech_x_4algo_16x16",
         runs["reference"]["wall_s"] * 1e6,
         f"cluster_runs={runs['reference']['cluster_stage_runs']}"),
        ("flow/speedup", 0.0,
         f"x{speedup:.2f}_bit_identical={identical}"),
    ]


def bench_systolic_sim(fast: bool) -> List[Tuple[str, float, str]]:
    """Cycle-level fault-injection simulator throughput."""
    from repro.core import (RazorConfig, SystolicSim, TimingModel, TECH_NODES,
                            quadrant_floorplan)
    tm = TimingModel(n=16, tech=TECH_NODES["vtr-22nm"], seed=2021)
    fp = quadrant_floorplan(16).with_voltages([0.9, 0.9, 1.0, 1.0])
    sim = SystolicSim(tm, fp, RazorConfig())
    rng = np.random.default_rng(0)
    a, w = rng.normal(size=(64, 16)), rng.normal(size=(16, 16))
    us, (c, stats) = _time_us(lambda: sim.matmul(a, w), repeats=1)
    return [("systolic_sim/16x16_m64", us,
             f"rel_err={stats.rel_error:.2e}_replays={stats.replay_cycles}")]


def bench_kernels(fast: bool) -> List[Tuple[str, float, str]]:
    """Pallas kernels in interpret mode vs their oracles (correctness +
    wall time; interpret-mode numbers are NOT TPU performance)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.ops import (precision_mm, razor_mm, ssd_op,
                                   systolic_matmul, wkv6_op)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(k1, (256, 256), jnp.bfloat16)
    b = jax.random.normal(k2, (256, 256), jnp.bfloat16)
    vmap_ = jnp.full((2, 2), 0.9)
    vsafe = jnp.asarray([[0.8, 1.0], [0.8, 0.8]])
    out = []

    us, (c, flags) = _time_us(
        lambda: jax.block_until_ready(systolic_matmul(a, b, vmap_, vsafe)))
    c_ref, f_ref = ref.systolic_mac(a, b, vmap_, vsafe)
    out.append(("kernels/systolic_mac_256", us,
                f"flags_match={bool((np.array(flags) == np.array(f_ref)).all())}"))

    us, (c, fl, rel) = _time_us(
        lambda: jax.block_until_ready(razor_mm(a, b)))
    out.append(("kernels/razor_matmul_256", us,
                f"max_tile_rel={float(np.array(rel).max()):.3f}"))

    tiers = jnp.asarray([[0, 1], [2, 0]], jnp.int32)
    us, c = _time_us(lambda: jax.block_until_ready(precision_mm(a, b, tiers)))
    out.append(("kernels/precision_island_256", us, "tiers=int4/int8/f32"))

    bs, s, h, p = 1, 128, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    r_, k_, v_ = (jax.random.normal(ks[i], (bs, s, h, p)) for i in range(3))
    w_log = -jnp.exp(jax.random.normal(ks[3], (bs, s, h, p)) * 0.5)
    u = jax.random.normal(ks[4], (h, p)) * 0.1
    s0 = jnp.zeros((bs, h, p, p))
    us, (y, _) = _time_us(
        lambda: jax.block_until_ready(wkv6_op(r_, k_, v_, w_log, u, s0,
                                              chunk=32)))
    y_ref, _ = ref.wkv6(r_, k_, v_, w_log, u, s0)
    err = float(jnp.abs(y - y_ref).max())
    out.append(("kernels/wkv6_b1s128", us, f"max_err_vs_ref={err:.2e}"))

    n = 8
    x = jax.random.normal(ks[0], (bs, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bs, s, h)))
    A_log = jax.random.normal(ks[2], (h,)) * 0.3
    B = jax.random.normal(ks[3], (bs, s, n))
    C = jax.random.normal(ks[4], (bs, s, n))
    D = jnp.ones((h,))
    st = jnp.zeros((bs, h, n, p))
    us, (y, _) = _time_us(
        lambda: jax.block_until_ready(ssd_op(x, dt, A_log, B, C, D, st,
                                             chunk=32)))
    y_ref, _ = ref.ssd(x, dt, A_log, B, C, D, st)
    err = float(jnp.abs(y - y_ref).max())
    out.append(("kernels/ssd_chunk_b1s128", us, f"max_err_vs_ref={err:.2e}"))
    return out


def bench_power_report(fast: bool) -> List[Tuple[str, float, str]]:
    """Paper power model applied to three dry-run cells' MAC counts."""
    from repro.roofline.power_report import power_row
    out = []
    cells = [("qwen1.5-110b", "train_4k"), ("rwkv6-1.6b", "decode_32k"),
             ("llama4-scout-17b-a16e", "prefill_32k")]
    for arch, shape in cells:
        us, row = _time_us(lambda a=arch, s=shape: power_row(a, s), repeats=1)
        out.append((f"power_report/{arch}_{shape}", us,
                    f"runtime_saving={row.runtime_saving_pct:.1f}%"
                    f"_precision={row.precision_saving_pct:.1f}%"))
    return out


def bench_serve(fast: bool) -> List[Tuple[str, float, str]]:
    """Continuous vs wave engine on one mixed smoke workload (CPU); writes
    the full telemetry comparison to BENCH_serve.json."""
    import jax
    from repro.configs import get_config
    from repro.models import model_api
    from repro.serve import Request, ServeEngine, WaveServeEngine
    cfg = get_config("starcoder2-3b", smoke=True)
    params = model_api(cfg).init_params(jax.random.PRNGKey(0))
    n_req = 4 if fast else 8

    def workload():
        return [Request(uid=uid,
                        prompt=rng.integers(3, cfg.vocab_size,
                                            int(rng.integers(1, 7))).tolist(),
                        max_new_tokens=int(rng.integers(2, 8)))
                for uid in range(n_req)]

    rows, engines = [], {}
    for name, engine_cls in (("continuous", ServeEngine),
                             ("wave", WaveServeEngine)):
        rng = np.random.default_rng(0)          # identical request sets

        def serve(engine_cls=engine_cls):
            eng = engine_cls(cfg, params, slots=2, max_len=48)
            for req in workload():
                eng.submit(req)
            return eng.run_until_drained()

        us, stats = _time_us(serve, repeats=1)
        engines[name] = {"us_per_call": us, **stats.to_dict()}
        rows.append((f"serve/{name}_{n_req}req", us,
                     f"model_steps={stats.model_steps}"
                     f"_tok_per_s={stats.tokens_generated / (us / 1e6):.1f}"))
    saved = 1 - engines["continuous"]["model_steps"] \
        / max(engines["wave"]["model_steps"], 1)
    payload = bench_payload(
        "serve",
        sum(e["us_per_call"] for e in engines.values()) / 1e6,
        {"arch": cfg.name, "requests": n_req, "slots": 2, "max_len": 48},
        **engines, model_steps_saved_frac=saved)
    with open(_json_path("BENCH_serve.json"), "w") as f:
        json.dump(payload, f, indent=2)
    rows.append(("serve/steps_saved", 0.0, f"saved_frac={saved:.2f}"))
    return rows


def bench_hwloop(fast: bool) -> List[Tuple[str, float, str]]:
    """Hardware-in-the-loop emulation (repro.hwloop): serving throughput with
    and without the emulated voltage-scaled accelerator attached, plus the
    energy/token vs replay-rate curve across rail operating points.  Writes
    BENCH_hwloop.json."""
    import jax
    from repro.configs import get_config
    from repro.flow import ArtifactStore, FlowConfig
    from repro.flow import run as flow_run
    from repro.hwloop import EmulatedAccelerator, HwLoopSession
    from repro.models import model_api
    from repro.serve import Request, ServeEngine

    mcfg = get_config("starcoder2-3b", smoke=True)
    params = model_api(mcfg).init_params(jax.random.PRNGKey(0))
    fcfg = FlowConfig(array_n=8, tech="vtr-22nm", max_trials=8, seed=2021)
    n_req = 3 if fast else 6
    rows: List[Tuple[str, float, str]] = []
    serve_payload: Dict = {}
    # one flow-artifact store shared by every session construction, so the
    # warmup and timed invocations both cache-hit the CAD-flow prefix
    store = ArtifactStore()

    for name in ("ideal", "hwloop"):

        def serve(name=name):
            # fresh session (and rng -> identical workload) per invocation:
            # _time_us calls serve() twice (warmup + timed), and the reported
            # telemetry must cover exactly the run the timing covers
            session = (HwLoopSession(fcfg, probe_rows=8, rail_margin=0.02,
                                     store=store)
                       if name == "hwloop" else None)
            rng = np.random.default_rng(0)
            eng = ServeEngine(mcfg, params, slots=2, max_len=48,
                              hwloop=session)
            for uid in range(n_req):
                eng.submit(Request(
                    uid=uid,
                    prompt=rng.integers(3, mcfg.vocab_size,
                                        int(rng.integers(1, 5))).tolist(),
                    max_new_tokens=int(rng.integers(2, 6))))
            return eng.run_until_drained()

        us, stats = _time_us(serve, repeats=1)
        tok_per_s = stats.tokens_generated / (us / 1e6)
        serve_payload[name] = {
            "us_per_call": us, "tok_per_s": tok_per_s,
            "model_steps": stats.model_steps,
            "telemetry": stats.hwloop,
            "step_flags_nonempty": bool(stats.hwloop_step_flags),
        }
        derived = f"tok_per_s={tok_per_s:.1f}"
        if stats.hwloop:
            derived += (f"_energy_per_tok="
                        f"{stats.hwloop['energy_per_token_j']:.3g}J")
        rows.append((f"hwloop/serve_{name}_{n_req}req", us, derived))
    overhead_pct = 100.0 * (
        serve_payload["ideal"]["tok_per_s"]
        / max(serve_payload["hwloop"]["tok_per_s"], 1e-9) - 1.0)

    # energy/token vs replay-rate across rail operating points: the same
    # calibrated design, rails scaled into (and past) the failure region
    rep = flow_run(fcfg)
    points = []
    for scale in (1.0, 0.97, 0.94, 0.9):
        accel = EmulatedAccelerator.from_flow(
            rep, fcfg, rails=np.asarray(rep.runtime_v) * scale)
        rng = np.random.default_rng(7)
        rel, steps = [], 8
        for _ in range(steps):
            _, tel = accel.matmul(rng.normal(size=(16, 8)),
                                  rng.normal(size=(8, 8)))
            rel.append(tel.rel_error)
        accel.ledger.add_tokens(steps)
        led = accel.ledger
        points.append({
            "rail_scale": scale,
            "rails_v": accel.rails.tolist(),
            "energy_per_token_j": led.energy_per_token_j,
            "replay_rate": led.replay_rate,
            "rel_error_mean": float(np.mean(rel)),
        })
        rows.append((f"hwloop/operating_point_x{scale}", 0.0,
                     f"energy_per_tok={led.energy_per_token_j:.3g}J"
                     f"_replay_rate={led.replay_rate:.2e}"
                     f"_rel_err={float(np.mean(rel)):.2e}"))
    payload = bench_payload(
        "hwloop",
        sum(e["us_per_call"] for e in serve_payload.values()) / 1e6,
        {"arch": mcfg.name, "requests": n_req, "slots": 2, "max_len": 48,
         "flow": fcfg.to_dict()},
        serve=serve_payload, emulation_overhead_pct=overhead_pct,
        operating_points=points)
    with open(_json_path("BENCH_hwloop.json"), "w") as f:
        json.dump(payload, f, indent=2)
    return rows


def bench_traffic(fast: bool) -> List[Tuple[str, float, str]]:
    """Traffic-trace overload envelope (repro.server): seeded Poisson /
    heavy-tailed workloads replayed deterministically in virtual time at
    1x/2x/4x the deployment's serving capacity, per execution backend.
    Reports p50/p99 TTFT, tokens/s, and shed rate; writes
    BENCH_traffic.json.  All latency numbers come from the injected
    VirtualClock, so they are bit-reproducible across machines."""
    import jax
    from repro.configs import get_config
    from repro.models import model_api
    from repro.serve import ServeEngine
    from repro.server import (LoadHarness, TrafficConfig, TrafficGenerator,
                              VirtualClock, overload_rate_rps)

    mcfg = get_config("starcoder2-3b", smoke=True)
    params = model_api(mcfg).init_params(jax.random.PRNGKey(0))
    slots, max_len, max_pending, step_cost_s, seed = 2, 32, 6, 0.02, 0
    duration_s = 1.5 if fast else 4.0
    backends = ("ideal",) if fast else ("ideal", "emulated")
    base = dict(duration_s=duration_s, seed=seed, max_prompt_len=8,
                max_gen_len=8, prompt_len_log_mean=0.8,
                prompt_len_log_sigma=0.5, gen_len_log_mean=1.0,
                gen_len_log_sigma=0.5, diurnal_amplitude=0.5,
                diurnal_period_s=duration_s, vocab_size=mcfg.vocab_size)

    def make_backend(name):
        if name == "ideal":
            return None
        from repro.backend import EmulatedBackend
        from repro.flow import FlowConfig
        from repro.flow import run as flow_run
        fcfg = FlowConfig(array_n=8, tech="vtr-22nm", max_trials=8,
                          seed=2021)
        return EmulatedBackend.from_flow(flow_run(fcfg), fcfg)

    rows: List[Tuple[str, float, str]] = []
    per_backend: Dict[str, Dict] = {}
    elapsed = 0.0
    for backend in backends:
        levels: Dict[str, Dict] = {}
        for factor in (1.0, 2.0, 4.0):
            rate = overload_rate_rps(factor, slots, step_cost_s,
                                     TrafficConfig(**base))
            events = TrafficGenerator(
                TrafficConfig(rate_rps=rate, **base)).events()
            clock = VirtualClock()
            eng = ServeEngine(mcfg, params, slots=slots, max_len=max_len,
                              clock=clock, policy="priority",
                              max_pending=max_pending,
                              backend=make_backend(backend))
            m = LoadHarness(eng, clock, step_cost_s=step_cost_s) \
                .replay(events)
            levels[f"{factor:g}x"] = m.to_dict()
            elapsed += m.wall_s
            p99 = "n/a" if m.ttft_p99_s is None else f"{m.ttft_p99_s:.3f}s"
            rows.append((f"traffic/{backend}_x{factor:g}", m.wall_s * 1e6,
                         f"shed_rate={m.shed_rate:.2f}"
                         f"_p99_ttft={p99}"
                         f"_tok_per_s={m.tokens_per_s:.1f}"))
        per_backend[backend] = levels
    payload = bench_payload(
        "traffic", elapsed,
        {"arch": mcfg.name, "slots": slots, "max_len": max_len,
         "max_pending": max_pending, "step_cost_s": step_cost_s,
         "seed": seed, "policy": "priority", "traffic": base},
        overload_factors=[1.0, 2.0, 4.0],
        backends=per_backend)
    with open(_json_path("BENCH_traffic.json"), "w") as f:
        json.dump(payload, f, indent=2)
    return rows


def bench_accuracy_voltage(fast: bool) -> List[Tuple[str, float, str]]:
    """BEYOND PAPER: the paper's stated future work (ii) — the trade-off
    between DNN accuracy (timing-failure corruption) and power as voltage
    drops through the critical region, measured on the fault-injecting
    systolic simulator (16x16, vtr-22nm)."""
    from repro.core import (RazorConfig, SystolicSim, TimingModel, TECH_NODES,
                            model_for, quadrant_floorplan)
    tm = TimingModel(n=16, tech=TECH_NODES["vtr-22nm"], seed=2021)
    pm = model_for("vtr-22nm")
    rng = np.random.default_rng(0)
    a, w = rng.normal(size=(48, 16)), rng.normal(size=(16, 16))
    out = []
    vmax = float(tm.min_safe_voltage().max())
    for v in (1.0, round(vmax + 0.02, 3), round(vmax - 0.01, 3),
              round(vmax - 0.05, 3), 0.6):
        fp = quadrant_floorplan(16).with_voltages([v] * 4)
        sim = SystolicSim(tm, fp, RazorConfig())

        def run(sim=sim):
            return sim.matmul(a, w)

        us, (c, stats) = _time_us(run, repeats=1)
        power = pm.partitioned_mw(16, [v] * 4, v_ref=1.0)
        out.append((f"accuracy_voltage/v{v}", us,
                    f"rel_err={stats.rel_error:.2e}"
                    f"_replays={stats.replay_cycles}"
                    f"_silent={int(stats.silent.sum())}"
                    f"_power={power:.0f}mW"))
    return out


def bench_analysis(fast: bool) -> List[Tuple[str, float, str]]:
    """jaxpr census over the family configs: host round-trips (pure_callback),
    dot count and flop estimate per model call under reference routing —
    ROADMAP item 1's worklist, written to BENCH_analysis.json and pinned by
    the lint-invariants CI gate."""
    from repro.analysis import CENSUS_ARCHS, census_config

    archs = CENSUS_ARCHS[:2] if fast else list(CENSUS_ARCHS)
    out: List[Tuple[str, float, str]] = []
    configs: Dict[str, Dict] = {}
    t_all = time.perf_counter()
    for arch in archs:
        t0 = time.perf_counter()
        report = census_config(arch, backend="reference")
        us = (time.perf_counter() - t0) * 1e6
        configs[arch] = report
        for phase in ("prefill", "decode"):
            c = report.get(phase)
            if c is None:
                continue
            out.append((
                f"analysis/{arch}_{phase}", us,
                f"callbacks={c['pure_callbacks']}_dots={c['dots']}"
                f"_flops={c['flops']:.3e}"))
    payload = bench_payload(
        "analysis", time.perf_counter() - t_all,
        {"archs": archs, "backend": "reference"},
        census=configs)
    with open(_json_path("BENCH_analysis.json"), "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    return out


def bench_resilience(fast: bool) -> List[Tuple[str, float, str]]:
    """ABFT guard economics + end-to-end chaos campaign
    (repro.resilience): (a) guard overhead per mode at nominal rails,
    (b) detection coverage / corrected rate / silent escapes per corruption
    model at crash-region rails with the escalation ladder disabled (pure
    verification), (c) the reduced-scale fault campaign over the full
    serving stack.  Writes BENCH_resilience.json; the CI resilience gate
    (``--resilience-gate``) pins abft silent escapes to zero and the
    campaign to all-green."""
    from repro.backend import EmulatedBackend
    from repro.resilience import GuardedBackend
    from repro.resilience.chaos import V_CRASH, run_campaign

    rng = np.random.default_rng(0)
    shapes = [(8, 8, 8), (16, 24, 8), (12, 40, 20)]
    # integer-valued operands: checksums are exact in f64, so clean GEMMs
    # match the ideal product bit for bit and every mismatch is injected
    ops = [(rng.integers(-4, 5, size=(m, k)).astype(np.float64),
            rng.integers(-4, 5, size=(k, n)).astype(np.float64))
           for m, k, n in shapes]
    rows: List[Tuple[str, float, str]] = []
    t_all = time.perf_counter()

    # (a) verification overhead at nominal (fault-free) rails
    overhead: Dict[str, Dict] = {}
    for mode in ("unguarded", "freivalds", "abft"):
        be = EmulatedBackend.nominal() if mode == "unguarded" else \
            GuardedBackend(EmulatedBackend.nominal(), mode=mode)

        def run(be=be):
            for a, b in ops:
                be.matmul(a, b)

        us, _ = _time_us(run, repeats=3 if fast else 10)
        overhead[mode] = {"us_per_3gemms": us}
        rows.append((f"resilience/nominal_{mode}", us, "faults=none"))
    for mode in ("freivalds", "abft"):
        pct = 100.0 * (overhead[mode]["us_per_3gemms"]
                       / max(overhead["unguarded"]["us_per_3gemms"], 1e-9)
                       - 1.0)
        overhead[mode]["overhead_pct"] = pct
        rows.append((f"resilience/overhead_{mode}", 0.0,
                     f"overhead={pct:.1f}%"))

    # (b) detection coverage at crash-region rails, ladder disabled: no
    # retries, no heal, fail_open — what the verifier alone sees
    rounds = 5 if fast else 20
    sweep: Dict[str, Dict] = {}
    for mode in ("freivalds", "abft"):
        sweep[mode] = {}
        for corruption in ("bitflip", "stale", "tedrop"):
            guard = GuardedBackend(
                EmulatedBackend.nominal(corruption=corruption), mode=mode,
                policy="fail_open", max_retries=0, heal=False)
            accel = guard.accel
            accel.set_rails(np.full(accel.n_partitions, V_CRASH))
            srng = np.random.default_rng(7)
            n = corrupted = detected = corrected = escapes = 0
            for _ in range(rounds):
                for m, k, nn in shapes:
                    a = srng.integers(-4, 5, size=(m, k)).astype(np.float64)
                    b = srng.integers(-4, 5, size=(k, nn)).astype(np.float64)
                    out, tel = guard.matmul(a, b)
                    bad = not np.array_equal(np.asarray(out), a @ b)
                    n += 1
                    corrupted += int(bad or tel.guard_detected > 0)
                    detected += int(tel.guard_detected > 0)
                    corrected += int(tel.guard_corrected > 0)
                    escapes += int(bad and tel.guard_detected == 0)
            cov = detected / max(corrupted, 1)
            sweep[mode][corruption] = {
                "gemms": n, "corrupted": corrupted, "detected": detected,
                "corrected": corrected, "silent_escapes": escapes,
                "detection_coverage": cov,
                "corrupted_rate": corrupted / n,
            }
            rows.append((f"resilience/{mode}_{corruption}", 0.0,
                         f"coverage={cov:.2f}_corrected={corrected}"
                         f"_escapes={escapes}"))

    # (c) the full-stack chaos campaign (engine + HTTP frontend + client)
    report = run_campaign(fast=True)
    rows.append(("resilience/campaign", report.elapsed_s * 1e6,
                 f"ok={report.ok}_crashes={report.crashes}"
                 f"_corrupted_streams={report.corrupted_streams}"))

    payload = bench_payload(
        "resilience", time.perf_counter() - t_all,
        {"shapes": shapes, "rounds": rounds, "v_crash": V_CRASH, "seed": 0,
         "tech": "vtr-22nm", "array_n": 8},
        overhead=overhead, corruption_sweep=sweep,
        campaign=report.to_dict())
    with open(_json_path("BENCH_resilience.json"), "w") as f:
        json.dump(payload, f, indent=2)
    return rows


def bench_obs(fast: bool) -> List[Tuple[str, float, str]]:
    """Observability overhead + determinism (repro.obs): (a) the same
    seeded workload served with full tracing/flight-recording
    (``ObsBus(enabled=True)``) vs counters-only
    (``ObsBus(enabled=False)``) — the marginal cost of the optional
    instrumentation, min-of-repeats; (b) two identical virtual-time
    ``LoadHarness`` replays must render bit-identical metric snapshots.
    Writes BENCH_obs.json; ``--obs-overhead-gate`` pins (a) under a
    percentage and (b) to True."""
    import jax
    from repro.configs import get_config
    from repro.models import model_api
    from repro.obs import ObsBus
    from repro.serve import Request, ServeEngine
    from repro.server import (LoadHarness, TrafficConfig, TrafficGenerator,
                              VirtualClock, overload_rate_rps)
    cfg = get_config("starcoder2-3b", smoke=True)
    params = model_api(cfg).init_params(jax.random.PRNGKey(0))
    n_req = 6 if fast else 12
    repeats = 3 if fast else 6
    t_all = time.perf_counter()

    def workload(rng):
        return [Request(uid=uid,
                        prompt=rng.integers(3, cfg.vocab_size,
                                            int(rng.integers(1, 7))).tolist(),
                        max_new_tokens=int(rng.integers(2, 8)))
                for uid in range(n_req)]

    def serve(enabled):
        rng = np.random.default_rng(0)          # identical request sets
        eng = ServeEngine(cfg, params, slots=2, max_len=48,
                          obs=ObsBus(enabled=enabled))
        for req in workload(rng):
            eng.submit(req)
        eng.run_until_drained()
        return eng

    # (a) marginal cost of tracing: warm both paths, then interleave the
    # timed repeats and keep the minimum (least-noise estimator)
    timings = {True: math.inf, False: math.inf}
    for enabled in (True, False):
        serve(enabled)                          # jit warmup / caches
    eng_on = None
    for _ in range(repeats):
        for enabled in (True, False):
            t0 = time.perf_counter()
            eng = serve(enabled)
            timings[enabled] = min(timings[enabled],
                                   time.perf_counter() - t0)
            if enabled:
                eng_on = eng
    overhead_pct = 100.0 * (timings[True] / max(timings[False], 1e-9) - 1.0)
    rows = [
        (f"obs/enabled_{n_req}req", timings[True] * 1e6,
         f"trace_events={eng_on.obs.recorder.total_recorded}"),
        (f"obs/disabled_{n_req}req", timings[False] * 1e6,
         "trace_events=0"),
        ("obs/overhead", 0.0, f"overhead={overhead_pct:.2f}%"),
    ]

    # (b) virtual-time determinism: identical replays, identical scrapes
    def virtual_run():
        clock = VirtualClock()
        eng = ServeEngine(cfg, params, slots=2, max_len=32, clock=clock,
                          policy="priority", max_pending=6,
                          obs=ObsBus(clock=clock))
        tcfg = TrafficConfig(
            rate_rps=overload_rate_rps(2.0, 2, 0.02, TrafficConfig()),
            duration_s=1.0, seed=0, max_prompt_len=8, max_gen_len=8,
            vocab_size=cfg.vocab_size)
        LoadHarness(eng, clock, step_cost_s=0.02).replay(
            TrafficGenerator(tcfg).events())
        return eng.obs.render_prometheus()

    snap_a, snap_b = virtual_run(), virtual_run()
    deterministic = snap_a == snap_b
    rows.append(("obs/deterministic_snapshots", 0.0,
                 f"bit_identical={deterministic}"))

    payload = bench_payload(
        "obs", time.perf_counter() - t_all,
        {"arch": cfg.name, "requests": n_req, "slots": 2, "max_len": 48,
         "repeats": repeats, "seed": 0},
        enabled_s=timings[True], disabled_s=timings[False],
        overhead_pct=overhead_pct,
        trace_events=eng_on.obs.recorder.total_recorded,
        metrics_exported=len(eng_on.obs.registry.names()),
        deterministic_snapshots=deterministic,
        snapshot_lines=len(snap_a.splitlines()))
    with open(_json_path("BENCH_obs.json"), "w") as f:
        json.dump(payload, f, indent=2)
    return rows


def bench_railscale(fast: bool) -> List[Tuple[str, float, str]]:
    """Closed-loop energy-aware rail autoscaling (repro.railscale): the
    same seeded traffic traces replayed in virtual time through (a) the
    abft-guarded emulated array pinned at static nominal rails — clean
    by construction (zero failure probability at V_nom), so its tokens
    are the ground truth for the emulated arithmetic — and (b) the
    closed loop: guarded emulated array + hwloop watchdog + threshold/
    pid autoscaler over the flow-characterized operating-point ladder.
    Headline (gated by ``--railscale-gate``): at 0.25x load the closed
    loop's energy/token drops strictly below static nominal with zero
    guard-uncorrected escapes and zero corrupted completions, and at
    peak the closed loop's p99 TTFT matches static within the SLO.
    Writes BENCH_railscale.json (scenarios x modes + the diurnal gauge
    timeline)."""
    import jax
    from repro.backend import EmulatedBackend
    from repro.configs import get_config
    from repro.flow import ArtifactStore, FlowConfig
    from repro.flow import run as flow_run
    from repro.hwloop import HwLoopSession
    from repro.models import model_api
    from repro.railscale import Autoscaler, OperatingPointTable
    from repro.resilience import GuardedBackend
    from repro.serve import ServeEngine
    from repro.server import (LoadHarness, TrafficConfig, TrafficGenerator,
                              VirtualClock, overload_rate_rps)

    mcfg = get_config("starcoder2-3b", smoke=True)
    params = model_api(mcfg).init_params(jax.random.PRNGKey(0))
    # a coarser virtual step than bench_traffic keeps the emulated
    # pure_callback model-call count (the real wall-clock cost) bounded
    slots, max_len, step_cost_s = 2, 32, 0.05
    slo_ttft_s = 2.0
    duration_s = 1.5 if fast else 3.0
    fcfg = FlowConfig(array_n=8, tech="vtr-22nm", max_trials=8, seed=2021)
    store = ArtifactStore()
    report = flow_run(fcfg, store=store)
    table = OperatingPointTable.characterize(
        report, fcfg, n_levels=4, probe_steps=4 if fast else 8,
        seed=fcfg.seed)
    nominal = table.rails(0)   # static baseline == ladder level 0

    base = dict(duration_s=duration_s, seed=0, max_prompt_len=8,
                max_gen_len=8, vocab_size=mcfg.vocab_size)
    scenarios = {
        "low": dict(factor=0.25),
        "peak": dict(factor=1.0),
        "diurnal": dict(factor=1.0, diurnal_amplitude=0.9,
                        diurnal_period_s=duration_s),
    }
    modes = ("static", "threshold") if fast else ("static", "threshold",
                                                  "pid")

    def run_mode(mode, events):
        clock = VirtualClock()
        kw: Dict[str, object] = {
            "backend": GuardedBackend(
                EmulatedBackend.from_flow(report, fcfg,
                                          rails=nominal.copy()),
                mode="abft", policy="fail_open")}
        if mode != "static":
            kw["hwloop"] = HwLoopSession(fcfg, probe_rows=8,
                                         rail_margin=0.02, store=store)
            # faster cadence than the serving default: the short virtual
            # trace must leave room for a full descent to the floor
            kw["autoscaler"] = Autoscaler(table, mode,
                                          slo_ttft_s=slo_ttft_s,
                                          start_level=0, decide_every=2,
                                          dwell_steps=4)
        eng = ServeEngine(mcfg, params, slots=slots, max_len=max_len,
                          clock=clock, **kw)
        harness = LoadHarness(eng, clock, step_cost_s=step_cost_s,
                              sample_every_s=0.1)
        m = harness.replay(events)
        tokens = {r.uid: list(r.out_tokens) for r in harness.requests
                  if r.done and not r.truncated and not r.shed}
        bs = eng.backend.summary()
        out = {"metrics": m.to_dict(), "tokens": tokens,
               "samples": harness.samples,
               "energy_per_token_j": bs.get("energy_per_token_j"),
               "guard_uncorrected": int(bs.get("guard_uncorrected", 0)),
               "flags": int(bs.get("flags", 0)),
               "replays": int(bs.get("replays", 0))}
        if eng.autoscaler is not None:
            out["railscale"] = eng.autoscaler.summary()
        return out

    rows: List[Tuple[str, float, str]] = []
    results: Dict[str, Dict] = {}
    t_all = time.perf_counter()
    for name, spec in scenarios.items():
        spec = dict(spec)
        factor = spec.pop("factor")
        tcfg = TrafficConfig(
            rate_rps=overload_rate_rps(factor, slots, step_cost_s,
                                       TrafficConfig(**base)),
            **base, **spec)
        events = TrafficGenerator(tcfg).events()
        reference: Dict[int, List[int]] = {}
        per_mode: Dict[str, Dict] = {}
        for mode in modes:
            t0 = time.perf_counter()
            res = run_mode(mode, events)
            wall = time.perf_counter() - t0
            # ground truth: the static-nominal run is fail-free by
            # construction (same emulated arithmetic, zero failure
            # probability at V_nom), so a closed-loop completion with
            # different tokens means the guard let corruption through
            if mode == "static":
                reference = res.pop("tokens")
                res["corrupted_completions"] = 0
            else:
                res["corrupted_completions"] = sum(
                    1 for uid, toks in res.pop("tokens").items()
                    if toks != reference.get(uid))
            m = res["metrics"]
            e = res["energy_per_token_j"]
            rows.append((
                f"railscale/{name}_{mode}", wall * 1e6,
                f"energy_per_token={'n/a' if e is None else f'{e:.3e}'}"
                f"_p99_ttft={m['ttft_p99_s'] if m['ttft_p99_s'] is None else round(m['ttft_p99_s'], 3)}"
                f"_corrupted={res['corrupted_completions']}"
                + (f"_level={res['railscale']['level']}"
                   f"_transitions={res['railscale']['transitions']}"
                   if "railscale" in res else "")))
            per_mode[mode] = res
        results[name] = {"factor": factor,
                         "reference_completed": len(reference),
                         "modes": per_mode}

    payload = bench_payload(
        "railscale", time.perf_counter() - t_all,
        {"arch": mcfg.name, "slots": slots, "max_len": max_len,
         "step_cost_s": step_cost_s, "seed": 0, "array_n": fcfg.array_n,
         "tech": fcfg.tech, "slo_ttft_s": slo_ttft_s,
         "duration_s": duration_s, "guard": "abft", "traffic": base},
        table={"levels": len(table), "meta": table.meta,
               "points": [p.to_dict() for p in table.points]},
        modes=list(modes),
        scenarios=results)
    with open(_json_path("BENCH_railscale.json"), "w") as f:
        json.dump(payload, f, indent=2)
    return rows


BENCHES: Dict[str, Callable] = {
    "analysis": bench_analysis,
    "tableII": bench_tableII,
    "fig15_16": bench_fig15_16,
    "clustering": bench_clustering,
    "cadflow": bench_cadflow,
    "flow_sweep": bench_flow_sweep,
    "flow": bench_flow,
    "systolic_sim": bench_systolic_sim,
    "kernels": bench_kernels,
    "power_report": bench_power_report,
    "serve": bench_serve,
    "hwloop": bench_hwloop,
    "traffic": bench_traffic,
    "accuracy_voltage": bench_accuracy_voltage,
    "resilience": bench_resilience,
    "obs": bench_obs,
    "railscale": bench_railscale,
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", choices=sorted(BENCHES), default=None)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--out-dir", default=".",
                    help="directory for BENCH_*.json artifacts (default: CWD)")
    ap.add_argument("--json-out", default=None,
                    help="exact JSON artifact path; only meaningful with "
                         "--only on a scenario that writes one")
    ap.add_argument("--min-flow-speedup", type=float, default=None,
                    help="fail (exit 1) unless the flow scenario's vectorized "
                         "sweep beats the reference by at least this factor")
    ap.add_argument("--resilience-gate", action="store_true",
                    help="fail (exit 1) unless the resilience scenario shows "
                         "zero abft silent escapes and an all-green chaos "
                         "campaign")
    ap.add_argument("--obs-overhead-gate", type=float, default=None,
                    metavar="PCT",
                    help="fail (exit 1) unless the obs scenario's tracing "
                         "overhead is below PCT%% and virtual-time metric "
                         "snapshots are bit-identical")
    ap.add_argument("--railscale-gate", action="store_true",
                    help="fail (exit 1) unless the railscale scenario shows "
                         "closed-loop energy/token at 0.25x load strictly "
                         "below static nominal, zero guard-uncorrected "
                         "escapes, zero corrupted completions, and peak p99 "
                         "TTFT within the SLO and no worse than static")
    args = ap.parse_args()
    enable_compile_cache()
    if args.json_out and not args.only:
        ap.error("--json-out requires --only (it names a single artifact)")
    _OUT["dir"] = args.out_dir
    _OUT["json_out"] = args.json_out

    names = [args.only] if args.only else list(BENCHES)
    if args.min_flow_speedup is not None and "flow" not in names:
        ap.error("--min-flow-speedup requires the flow scenario to run")
    if args.resilience_gate and "resilience" not in names:
        ap.error("--resilience-gate requires the resilience scenario to run")
    if args.obs_overhead_gate is not None and "obs" not in names:
        ap.error("--obs-overhead-gate requires the obs scenario to run")
    if args.railscale_gate and "railscale" not in names:
        ap.error("--railscale-gate requires the railscale scenario to run")
    print("name,us_per_call,derived")
    for name in names:
        for row_name, us, derived in BENCHES[name](args.fast):
            print(f"{row_name},{us:.1f},{derived}", flush=True)

    if args.min_flow_speedup is not None:
        path = args.json_out if (args.json_out and args.only == "flow") \
            else os.path.join(args.out_dir, "BENCH_flow.json")
        with open(path) as f:
            payload = json.load(f)
        ok = (payload["speedup"] >= args.min_flow_speedup
              and payload["bit_identical_reports"])
        print(f"flow gate: speedup={payload['speedup']:.2f} "
              f"(need >= {args.min_flow_speedup}), "
              f"bit_identical={payload['bit_identical_reports']} -> "
              f"{'PASS' if ok else 'FAIL'}", flush=True)
        if not ok:
            sys.exit(1)

    if args.resilience_gate:
        path = args.json_out if (args.json_out
                                 and args.only == "resilience") \
            else os.path.join(args.out_dir, "BENCH_resilience.json")
        with open(path) as f:
            payload = json.load(f)
        escapes = sum(c["silent_escapes"]
                      for c in payload["corruption_sweep"]["abft"].values())
        campaign_ok = payload["campaign"]["ok"] \
            and payload["campaign"]["crashes"] == 0 \
            and payload["campaign"]["corrupted_streams"] == 0
        ok = escapes == 0 and campaign_ok
        print(f"resilience gate: abft_silent_escapes={escapes} (need 0), "
              f"campaign_ok={campaign_ok} -> {'PASS' if ok else 'FAIL'}",
              flush=True)
        if not ok:
            sys.exit(1)

    if args.railscale_gate:
        path = args.json_out if (args.json_out and args.only == "railscale") \
            else os.path.join(args.out_dir, "BENCH_railscale.json")
        with open(path) as f:
            payload = json.load(f)
        slo = payload["config"]["slo_ttft_s"]
        closed_modes = [m for m in payload["modes"] if m != "static"]
        checks: List[Tuple[str, bool]] = []
        static_low = payload["scenarios"]["low"]["modes"]["static"]
        static_peak = payload["scenarios"]["peak"]["modes"]["static"]
        for mode in closed_modes:
            low = payload["scenarios"]["low"]["modes"][mode]
            peak = payload["scenarios"]["peak"]["modes"][mode]
            checks.append((
                f"{mode}: low-load energy/token "
                f"{low['energy_per_token_j']:.3e} < static "
                f"{static_low['energy_per_token_j']:.3e}",
                low["energy_per_token_j"]
                < static_low["energy_per_token_j"]))
            checks.append((
                f"{mode}: peak p99 TTFT {peak['metrics']['ttft_p99_s']:.3f}s"
                f" <= SLO {slo}s and <= static "
                f"{static_peak['metrics']['ttft_p99_s']:.3f}s",
                peak["metrics"]["ttft_p99_s"] <= slo
                and (peak["metrics"]["ttft_p99_s"]
                     <= static_peak["metrics"]["ttft_p99_s"] + 1e-9)))
            checks.append((
                f"{mode}: closed loop actually undervolted at low load",
                payload["scenarios"]["low"]["modes"][mode]["railscale"]
                ["transitions"]["down"] > 0))
        for name in payload["scenarios"]:
            for mode, res in payload["scenarios"][name]["modes"].items():
                checks.append((
                    f"{name}/{mode}: zero guard-uncorrected + zero "
                    f"corrupted completions",
                    res["guard_uncorrected"] == 0
                    and res["corrupted_completions"] == 0))
        ok = all(c for _, c in checks)
        for desc, c in checks:
            print(f"railscale gate: {desc} -> {'PASS' if c else 'FAIL'}",
                  flush=True)
        if not ok:
            sys.exit(1)

    if args.obs_overhead_gate is not None:
        path = args.json_out if (args.json_out and args.only == "obs") \
            else os.path.join(args.out_dir, "BENCH_obs.json")
        with open(path) as f:
            payload = json.load(f)
        ok = (payload["overhead_pct"] < args.obs_overhead_gate
              and payload["deterministic_snapshots"])
        print(f"obs gate: overhead={payload['overhead_pct']:.2f}% "
              f"(need < {args.obs_overhead_gate}), deterministic="
              f"{payload['deterministic_snapshots']} -> "
              f"{'PASS' if ok else 'FAIL'}", flush=True)
        if not ok:
            sys.exit(1)


if __name__ == "__main__":
    main()
