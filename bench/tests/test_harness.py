"""The benchmark's parts on hand-built inputs: traffic, readers, work
reconstruction, peaks, the cost model, and BENCHMARK.json itself."""

import json
import math
import re
from collections import Counter

import numpy as np
import pytest

from bench import cost, harness, records, steps, workload
from bench.peaks import peaks
from bench.stats import nearest_rank

ROOT = harness.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MIXES = {p.stem: workload.load_mix(p.stem)
         for p in (ROOT / "bench" / "traffic").glob("*.json")}
CONFIGS = {p.stem: json.loads(p.read_text())
           for p in (ROOT / "bench" / "configs").glob("*.json")}


# ---- traffic -----------------------------------------------------------------

@pytest.mark.parametrize("mix", ["chat", "code_burst"])
def test_open_schedule_is_deterministic_under_the_seed(mix):
    a = workload.open_schedule(MIXES[mix], 2 ** 40 + 7, 20.0, 1000)
    b = workload.open_schedule(MIXES[mix], 2 ** 40 + 7, 20.0, 1000)
    c = workload.open_schedule(MIXES[mix], 8, 20.0, 1000)
    assert a == b
    assert a != c
    # another seed draws other token ids, never other times or sizes
    assert [(i.due_s, len(i.prompt), i.max_new_tokens) for i in a] == \
        [(i.due_s, len(i.prompt), i.max_new_tokens) for i in c]


@pytest.mark.parametrize("mix", ["chat", "code_burst"])
def test_every_seed_gets_the_same_sizes(mix):
    def sizes(seed):
        s = workload.open_schedule(MIXES[mix], seed, 30.0, 1000)
        return (Counter(len(i.prompt) for i in s),
                Counter(i.max_new_tokens for i in s))
    assert sizes(1) == sizes(99)
    # the gaps between arrivals are the stratified set but the one that
    # runs past the window's close
    n = len(workload.open_schedule(MIXES[mix], 1, 30.0, 1000))
    scale = 30.0 / (n / MIXES[mix]["rate_rps"])
    full = Counter(np.round(workload.gaps(MIXES[mix], n) * scale, 9))
    for seed in (1, 99):
        s = workload.open_schedule(MIXES[mix], seed, 30.0, 1000)
        d = Counter(np.round(np.diff([i.due_s for i in s]), 9))
        assert not d - full and sum((full - d).values()) == 1


@pytest.mark.parametrize("mix", ["chat", "code_burst"])
def test_open_schedule_honours_rate_clips_and_rounding(mix):
    m = MIXES[mix]
    seconds = 51.0
    s = workload.open_schedule(m, 3, seconds, 1000)
    assert len(s) == round(m["rate_rps"] * seconds)
    assert all(0.0 <= i.due_s < seconds for i in s)
    p, o = m["prompt"], m["output"]
    for i in s:
        assert p["min"] <= len(i.prompt) <= p["max"]
        assert len(i.prompt) % p["round_to"] == 0
        assert o["min"] <= i.max_new_tokens <= o["max"]
        assert all(3 <= t < 1000 for t in i.prompt)


@pytest.mark.parametrize("mix,want", [("chat", 1.0), ("code_burst", 2.0)])
def test_inter_arrival_cv(mix, want):
    g = workload.gaps(MIXES[mix], 4000)
    assert g.std() / g.mean() == pytest.approx(want, rel=0.1)
    assert g.mean() == pytest.approx(1 / MIXES[mix]["rate_rps"])


def test_lengths_follow_the_lognormal_median():
    spec = {"median": 96, "sigma": 0.8, "min": 16, "max": 512}
    n = workload.lengths(spec, 1001)
    assert np.median(n) == 96
    assert n.min() >= 16 and n.max() <= 512


def test_closed_pool_blocks_are_full_stratified_sets():
    m = MIXES["chat_closed"]
    pool = workload.closed_pool(m, 5, 3, 1000)
    other = workload.closed_pool(m, 6, 3, 1000)
    c = m["clients"]
    assert len(pool) == 3 * c
    assert [(len(i.prompt), i.max_new_tokens) for i in pool] == \
        [(len(i.prompt), i.max_new_tokens) for i in other]
    assert [i.prompt for i in pool] != [i.prompt for i in other]
    blocks = [sorted(len(i.prompt) for i in pool[k * c:(k + 1) * c])
              for k in range(3)]
    assert blocks[0] == blocks[1] == blocks[2]
    assert all(i.due_s == 0.0 for i in pool)


def test_prompt_lengths_to_warm():
    assert workload.prompt_lengths(MIXES["chat"]) == [256, 512, 768, 1024,
                                                      1280, 1536]
    assert workload.prompt_lengths(MIXES["code_burst"])[-1] == 1792
    assert workload.prompt_lengths(MIXES["chat_closed"]) == []


# ---- readers ------------------------------------------------------------------

def _req(due, sent, times, n=None, status="completed", ttft=None):
    r = records.new_record([5] * 4, n if n is not None else len(times), due)
    r.update(sent=sent, times=list(times), tokens=[7] * len(times),
             status=status if times else None,
             engine_ttft_s=ttft if ttft is not None else 0.0)
    return r


def _record(**kw):
    rec = {"cell": "x", "seconds": 10.0, "setup_s": 12.5,
           "window": (100.0, 110.0), "requests": [], "counters": {},
           "events": [], "model": CONFIGS["phi4-mini-3.8b"]["model"],
           "peaks": peaks("TPU v5 lite"), "trace": None,
           "trace_window": (100.0, 110.0)}
    rec.update(kw)
    return rec


def test_latency_and_rate_readers():
    reqs = [_req(100.0 + i, 100.0 + i, [100.5 + i + 0.1 * k for k in range(5)])
            for i in range(9)]
    reqs[0]["times"][2] += 0.35                        # one long gap
    rec = _record(requests=reqs)
    gaps = [g for r in reqs for g in records.gaps(r)]
    assert harness.reader("itl_p50_s")(rec) == nearest_rank(gaps, 0.5)
    assert harness.reader("itl_p50_s")(rec) == pytest.approx(0.1)
    assert harness.reader("itl_p99_s")(rec) == pytest.approx(0.45)
    inside = sum(1 for r in reqs for t in r["times"] if 100.0 <= t <= 110.0)
    assert harness.reader("output_tok_s")(rec) == inside / 10.0
    assert harness.reader("setup_s")(rec) == 12.5
    # first tokens 0.5 s after due, but for one request 1.4 s
    reqs[8]["times"] = [t + 0.9 for t in reqs[8]["times"]]
    reqs.append(_req(109.0, None, []))                 # no token yet
    assert harness.reader("ttft_p90_s")(rec) == pytest.approx(1.4)


def test_queue_wait_reader():
    rec = _record(counters={"queue_wait_sum": 1.5, "queue_wait_count": 6})
    assert harness.reader("queue_wait_mean_s")(rec) == 0.25
    rec["counters"] = {"queue_wait_sum": 0.0, "queue_wait_count": 0}
    assert harness.reader("queue_wait_mean_s")(rec) is None


def test_nearest_rank():
    assert nearest_rank([3, 1, 2, 4], 0.5) == 2
    assert nearest_rank(list(range(1, 101)), 0.99) == 99
    assert nearest_rank([5.0], 0.9) == 5.0


def _events():
    """Two requests: uid 1 (prompt 10) from t=1.0, uid 2 (prompt 20) from
    t=2.5; decode steps at t=2, 3, 4; uid 1 finishes at t=3.5."""
    return [
        {"kind": "event", "name": "request_admitted", "t": 0.9, "uid": 1},
        {"kind": "span", "name": "prefill", "t": 0.9, "dur_s": 0.1, "uid": 1,
         "slot": 0, "prompt_len": 10},
        {"kind": "span", "name": "decode_step", "t": 2.0, "dur_s": 0.02,
         "active": 1},
        {"kind": "span", "name": "prefill", "t": 2.4, "dur_s": 0.1, "uid": 2,
         "slot": 1, "prompt_len": 20},
        {"kind": "event", "name": "request_finished", "t": 3.5, "uid": 1},
        {"kind": "span", "name": "decode_step", "t": 3.0, "dur_s": 0.04,
         "active": 2},
        {"kind": "span", "name": "decode_step", "t": 4.0, "dur_s": 0.03,
         "active": 1},
    ]


def test_work_rebuilt_from_engine_events():
    w = steps.work(_events(), 0.0, 10.0)
    assert w["prefills"] == [10, 20]
    assert w["decode_keys"] == [[11], [12, 21], [22]]
    late = steps.work(_events(), 2.5, 3.5)
    assert late == {"prefills": [], "decode_keys": [[12, 21]]}


def test_span_and_trace_readers():
    model = CONFIGS["phi4-mini-3.8b"]["model"]
    pk = peaks("TPU v5 lite")
    red = {"window_s": 4.0, "busy_s": 3.0,
           "programs": {"jit_prefill": {"device_s": 0.006, "count": 2},
                        "jit_decode_step": {"device_s": 0.06, "count": 3},
                        "jit_decode_step(7)": {"device_s": 0.06, "count": 3}},
           "device_ops": [], "idle_gaps": []}
    rec = _record(events=_events(), trace=red, trace_window=(0.0, 10.0),
                  window=(0.0, 10.0), model=model)
    assert harness.reader("decode_step_ms")(rec) == pytest.approx(30.0)
    assert harness.reader("prefill_ms_per_ktok")(rec) == pytest.approx(
        6.0 / 0.030)
    keys = [[11], [12, 21], [22]]
    need = sum(cost.decode_bytes(model, k) for k in keys) / 3
    assert harness.reader("decode_hbm_roofline")(rec) == pytest.approx(
        100 * need * 3 / pk["hbm_bytes_per_s"] / 0.06)
    assert harness.reader("prefill_mfu")(rec) == pytest.approx(
        100 * (cost.prefill_flops(model, 10) + cost.prefill_flops(model, 20))
        / (0.006 * pk["bf16_flops_per_s"]))
    assert harness.reader("decode_mfu")(rec) == pytest.approx(
        100 * sum(cost.decode_flops(model, k) for k in keys)
        / (0.06 * pk["bf16_flops_per_s"]))
    assert harness.reader("absorb_ms_per_tok")(rec) is None
    programs = {k: v for k, v in red["programs"].items()
                if not k.startswith("jit_prefill")}
    hybrid = dict(red, programs=dict(programs, **{
        "jit_decode_step(8)": {"device_s": 0.5, "count": 100}}))
    rec["trace"] = hybrid
    assert harness.reader("absorb_ms_per_tok")(rec) == pytest.approx(5.0)
    # a stretch with no batched step: the one variant that ran absorbs
    rec.update(trace=dict(red, programs={
        "jit_decode_step": {"device_s": 0.5, "count": 100},
        "jit_decode_step(8)": {"device_s": 0.5, "count": 100}}),
        trace_window=(5.0, 10.0))
    assert harness.reader("absorb_ms_per_tok")(rec) == pytest.approx(5.0)
    rec["trace_window"] = (0.0, 10.0)
    rec["trace"] = None
    for m in ("prefill_ms_per_ktok", "decode_hbm_roofline", "prefill_mfu",
              "decode_mfu", "absorb_ms_per_tok"):
        assert harness.reader(m)(rec) is None


# ---- peaks, cost ------------------------------------------------------------------

def test_peaks_refuse_an_unknown_device_kind():
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        peaks("TPU v9")


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_cost_matches_the_program_analytic_model(name):
    from repro.configs.base import ShapeConfig
    from repro.models import model_api, param_count
    from repro.roofline import analytic
    model = CONFIGS[name]["model"]
    cfg = harness.program_config(CONFIGS[name])
    b, s = 8, 1024
    # a decode step whose every row attends s positions
    want = analytic.forward_flops(cfg, ShapeConfig("d", s, b, "decode"))
    assert cost.decode_flops(model, [s] * b) == pytest.approx(want, rel=1e-12)
    # a prefill: analytic counts s^2/2 causal pairs and logits at every
    # position; cost counts s(s+1)/2 pairs and logits at the last one
    fwd = analytic.forward_flops(cfg, ShapeConfig("p", s, 1, "prefill"))
    extra = (cost.attention_flops(model, s) // 2
             - (s - 1) * cost.head_flops(model))
    assert cost.prefill_flops(model, s) == pytest.approx(fwd + extra,
                                                         rel=1e-12)
    params = param_count(model_api(cfg).param_specs())
    counts = cost.param_counts(model)
    assert counts["bf16"] + counts["f32"] == params


def test_decode_bytes_count_held_positions():
    model = CONFIGS["phi4-mini-3.8b"]["model"]
    kv = cost.kv_bytes_per_position(model)
    assert kv == 32 * 2 * 8 * 128 * 2
    base = cost.decode_bytes(model, [])
    assert base == cost.weight_bytes(model)
    assert cost.decode_bytes(model, [100, 300]) - base == (400 + 2) * kv
    z = CONFIGS["zamba2-2.7b"]["model"]
    assert cost.state_bytes_per_slot(z) == 54 * (80 * 64 * 64 * 4
                                                 + 3 * 5248 * 2)


# ---- the benchmark's own file ------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_file_is_consistent():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    cells = {w["name"]: w for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"])
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for c in m["workloads"]:
            assert c in e2e[m["moves"]].get("workloads", cells)
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] == 1
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        cell = harness.find_cell(BENCH, w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for c in BENCH["configs"]:
        conf = CONFIGS[c["name"]]
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert conf["name"] == c["name"] and c["reduced"] == conf["reduced"]
        assert (ROOT / "bench" / "refs" / f"{c['name']}.py").is_file()
        assert conf["limits"]
    n = len(BENCH["workloads"])
    assert n <= 24 and 1 <= BENCH["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_configuration_matches_the_program(name):
    """The file's sizes are the program's registered configuration's, and
    the reference's parameter layout is the program's tree."""
    import jax
    from repro.configs import get_config
    from repro.models import model_api
    from bench.refs import common
    conf = CONFIGS[name]
    base = get_config(conf["arch"])
    cfg = harness.program_config(conf)
    assert cfg == base
    want = jax.eval_shape(model_api(cfg).init_params, jax.random.PRNGKey(0))
    have = common.shapes(common.load(name).layout(conf["model"]))
    assert jax.tree.structure(want) == jax.tree.structure(have)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert cost.weight_bytes(conf["model"]) == sum(
        math.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(want))


def test_without_a_tpu_the_run_stops_before_any_result():
    with pytest.raises(harness.BenchError, match="TPU"):
        harness.require_chips(1)


def test_seed_key_takes_seeds_beyond_32_bits():
    import jax
    k1 = harness.seed_key(2 ** 33 + 5)
    k2 = harness.seed_key(5)
    assert not np.array_equal(jax.random.key_data(k1),
                              jax.random.key_data(k2))


def test_sample_holds_the_longest_and_is_seeded():
    recs = [_req(0.0, 0.0, [1.0] * n, ttft=0.1) for n in (3, 50, 7, 9, 11)]
    recs.append(_req(0.0, 0.0, [1.0], n=4))           # short: never sampled
    a = harness.sample(recs, 4)
    assert a[0] is recs[1]
    assert a == harness.sample(recs, 4)
    assert all(records.ok(r) for r in a)
    assert len(a) == 5


def test_stream_checks():
    good = _req(0.0, 0.0, [1.0, 2.0])
    short = _req(0.0, 0.0, [1.0], n=2)
    never = records.new_record([5], 2, 0.0)
    bad = _req(0.0, 0.0, [1.0])
    bad["tokens"] = [10 ** 9]
    assert harness.stream_checks([good, short, never, bad], 1000) == {
        "never_came": 1, "short_streams": 1, "tokens_outside_vocab": 1}
