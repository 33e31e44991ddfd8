"""One run of one cell: set-up, the measured window, the check, the result.

``run.py`` is the command; this module holds its parts so that the tools
under ``bench/tools`` and the tests can drive them.  A run:

1. finds the cell in ``BENCHMARK.json``, its configuration file, its mix
   file and a reader per metric (``bench/metrics/<name>.py``);
2. requires the chips the cell asks for (never falls back to the CPU);
3. makes the weights on the device in one jitted call from ``--seed``,
   builds ``ServeEngine`` and warms every shape the mix uses;
4. drives the mix into the engine from one thread of this process for
   ``--seconds`` (``Feeder``: the engine loop a server runs, without its
   HTTP layer), each request timed from when it was due, and waits for the
   requests of the window to finish (at most ``GRACE_S`` past it);
5. reads the peak device memory, frees the engine, and compares a seeded
   sample of the served requests with the plain float32 reference;
6. prints the end-to-end metrics (``--trace 0``) or, from a profiler trace
   of part of the window and the engine's spans, the per-layer metrics
   (``--trace 1``), and as its last line one JSON object.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import itertools
import json
import os
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from bench import (BenchError, cost, records, trace as tracemod,  # noqa: E402
                   workload)
from bench.peaks import peaks  # noqa: E402
from bench.refs import common  # noqa: E402

#: How long past the window's close the run waits for a request of the
#: window to finish; one that has not by then never came.
GRACE_S = 60.0
#: Longest stretch of the window that ``--trace 1`` records.
TRACE_S = 8.0
#: The check compares at least ``CHECK_TOKENS`` served tokens of at least
#: ``CHECK_MIN`` requests, and at most ``CHECK_REQUESTS`` requests.
CHECK_TOKENS, CHECK_MIN, CHECK_REQUESTS = 400, 3, 16
#: Engine spans put into the profiler's trace.
ENGINE_SPANS = ("prefill", "decode_step")


# ---- the benchmark's files -------------------------------------------------

def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file
    mix: Dict[str, Any]             # the traffic file
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _reports(metric: Dict[str, Any], cell: str,
             e2e: Optional[Sequence[str]] = None) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads``
    key lists, else every cell (an end-to-end metric) or every cell that
    reports the end-to-end metric it ``moves`` (``e2e``)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e is None or metric["moves"] in e2e


def find_cell(bench: Dict[str, Any], name: str, root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no cell {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf_entry["file"]).read_text())
    cost.blocks(config["model"], conf_entry["file"])
    mix = workload.load_mix(w["traffic"], root / "bench" / "traffic")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer)


def reader(metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---- process, chip and cache -----------------------------------------------

def process_start() -> float:
    """Wall-clock time at which this process started (Linux ``/proc``)."""
    try:
        fields = Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()
        ticks = int(fields[19])
        btime = next(int(line.split()[1]) for line in
                     Path("/proc/stat").read_text().splitlines()
                     if line.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


def require_chips(n: int):
    """The first device, which must be a TPU, and at least ``n`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise BenchError(f"needs {n} TPU chip(s); JAX found {len(devs)} "
                         f"{devs[0].platform} device(s) "
                         f"({devs[0].device_kind})")
    return devs[0]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache in ``<checkout>/.jax_cache``, or
    where ``JAX_COMPILATION_CACHE_DIR`` says; every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (beyond 32 bits too)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


# ---- the system under test ---------------------------------------------------

def program_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` for ``config["arch"]`` with every size
    the configuration file states."""
    from repro.configs import get_config
    base = get_config(config["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    cfg = dataclasses.replace(base, **{k: v for k, v in config["model"].items()
                                       if k in fields})
    if cfg.padded_vocab != cost.padded_vocab(config["model"]):
        raise BenchError(f"program pads the vocabulary to {cfg.padded_vocab}, "
                         f"the file to {cost.padded_vocab(config['model'])}")
    return cfg


def make_weights(config: Dict[str, Any], seed: int, root: Path = ROOT):
    """The benchmark's weights for ``config``, made on the device in one
    jitted call, checked against the program's parameter tree, and the
    configuration's reference (``<root>/bench/refs/<name>.py``)."""
    import jax
    from repro.models import model_api
    ref = common.load(config["name"], root / "bench" / "refs")
    layout = ref.layout(config["model"])
    want = jax.eval_shape(model_api(program_config(config)).init_params,
                          jax.random.PRNGKey(0))
    have = common.shapes(layout)
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise BenchError("the reference's parameter layout differs from the "
                         "program's")
    params = jax.jit(lambda k: common.init_params(layout, k))(seed_key(seed))
    return ref, jax.block_until_ready(params)


def annotating_tracer(clock):
    """A ``repro.obs`` tracer whose spans also appear in the profiler's
    trace, as ``jax.profiler.TraceAnnotation`` of the same name."""
    import jax
    from repro.obs.trace import Span, Tracer

    class AnnotatedSpan(Span):
        __slots__ = ("_ann",)

        def __init__(self, tracer, name, attrs):
            super().__init__(tracer, name, attrs)
            self._ann = jax.profiler.TraceAnnotation(name)
            self._ann.__enter__()

        def end(self) -> None:
            if not self._done:
                self._ann.__exit__(None, None, None)
            super().end()

    class AnnotatingTracer(Tracer):
        def span(self, name, **attrs):
            if not self.enabled:
                return super().span(name, **attrs)
            return AnnotatedSpan(self, name, attrs)

    return AnnotatingTracer(clock=clock)


def build_engine(config: Dict[str, Any], params, annotate: bool):
    from repro.obs import ObsBus
    from repro.serve import ServeEngine
    bus = ObsBus(clock=time.monotonic)
    if annotate:
        bus.tracer = annotating_tracer(time.monotonic)
        bus.tracer.add_sink(bus.recorder.record)
    events: List[Dict[str, Any]] = []
    bus.tracer.add_sink(events.append)
    eng = config["engine"]
    engine = ServeEngine(program_config(config), params, slots=eng["slots"],
                         max_len=eng["max_len"], backend=eng["backend"],
                         obs=bus)
    return engine, events


def warm_up(engine, mix: Dict[str, Any], vocab: int, seed: int) -> int:
    """Serve one two-token request per prompt length the mix can produce
    (prefill per length, slot inject, batched decode); a mix of unrounded
    lengths gets one request of its shortest length (prompts absorbed one
    token per step compile nothing per length)."""
    from repro.serve import Request
    lens = workload.prompt_lengths(mix) or [int(mix["prompt"]["min"])]
    rng = np.random.default_rng([seed, 1])
    for i, n in enumerate(lens):
        engine.submit(Request(uid=-1 - i, max_new_tokens=2,
                              prompt=rng.integers(3, vocab, n).tolist()))
    stats = engine.run_until_drained()
    if stats.completed < len(lens):
        raise BenchError(f"warm-up completed {stats.completed} of {len(lens)}")
    return len(lens)


def counters(engine) -> Dict[str, float]:
    """The engine's cumulative counters the readers difference."""
    reg = engine.obs.registry
    _, qsum, qn = reg.histogram("serve_queue_wait_seconds").snapshot()
    return {"queue_wait_sum": qsum, "queue_wait_count": qn}


# ---- the window --------------------------------------------------------------

@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    records: List[Dict[str, Any]]
    counters: Dict[str, float]
    trace_dir: Optional[str] = None
    trace_lo: float = 0.0
    trace_hi: float = 0.0


class Feeder:
    """Drives one ``ServeEngine`` from one thread, as a server's engine
    loop does: before each engine step it hands the engine every request
    that has come due, and steps while the engine has work.

    Open loop: ``items`` fall due at ``t0 + item.due_s``.  Closed loop:
    ``clients`` requests fall due at ``t0``, and each finished request's
    client sends its next one (from ``items`` in order) at once, while the
    window is open.  Tokens are stamped with ``time.monotonic()`` as the
    engine emits them."""

    def __init__(self, engine, items: Sequence[workload.Item], t0: float,
                 t1: float, clients: int = 0) -> None:
        self.engine = engine
        self.t0, self.t1 = t0, t1
        self.closed = clients > 0
        self.items = list(items)
        self.next = clients if self.closed else 0
        self.due: List[Dict[str, Any]] = []
        self.records: List[Dict[str, Any]] = []
        self.error: Optional[BaseException] = None
        self._uids = itertools.count()      # one per request submitted
        self._stop = threading.Event()
        if self.closed:
            self.due = [self._record(it, t0) for it in self.items[:clients]]
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-engine-feeder")

    def _record(self, item: workload.Item, due: float) -> Dict[str, Any]:
        rec = records.new_record(item.prompt, item.max_new_tokens, due)
        self.records.append(rec)
        return rec

    def _submit(self, rec: Dict[str, Any]) -> None:
        from repro.serve import Request

        def on_token(req, tok, rec=rec):
            rec["times"].append(time.monotonic())
            rec["tokens"].append(int(tok))

        def on_finish(req, rec=rec):
            rec["status"] = req.status
            rec["engine_ttft_s"] = req.ttft_s
            now = time.monotonic()
            if self.closed and now < self.t1 and self.next < len(self.items):
                self.due.append(self._record(self.items[self.next], now))
                self.next += 1

        rec["sent"] = time.monotonic()
        self.engine.submit(Request(uid=next(self._uids), prompt=rec["prompt"],
                                   max_new_tokens=rec["max_new_tokens"],
                                   on_token=on_token, on_finish=on_finish))

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                now = time.monotonic()
                while (not self.closed and self.next < len(self.items)
                       and self.t0 + self.items[self.next].due_s <= now):
                    it = self.items[self.next]
                    self.next += 1
                    self._submit(self._record(it, self.t0 + it.due_s))
                while self.due:
                    self._submit(self.due.pop(0))
                if not self.engine.scheduler.drained():
                    self.engine.step()
                    continue
                wake = self.t0 + self.items[self.next].due_s \
                    if not self.closed and self.next < len(self.items) \
                    else now + 0.005
                self._stop.wait(min(max(wake - time.monotonic(), 0.0), 0.005))
        except BaseException as e:       # surfaced by ``stop``
            self.error = e

    def start(self) -> None:
        self._thread.start()

    def finished(self) -> bool:
        """Every request of the window has come due and finished."""
        return (self.engine.scheduler.drained() and not self.due
                and (self.closed or self.next >= len(self.items))
                and all(r["status"] is not None for r in self.records))

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()
        if self.error is not None:
            raise BenchError(f"engine feeder failed: {self.error!r}") \
                from self.error


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # no per-call Python events
    return opts


def drive(engine, mix: Dict[str, Any], seed: int, seconds: float,
          vocab: int, trace_dir: Optional[str] = None,
          on_open: Optional[Callable[[], None]] = None) -> Window:
    """Drive the mix for ``seconds``, then wait for the window's requests
    (at most ``GRACE_S`` past its close); a traced run records
    ``TRACE_S`` from the middle of the window."""
    import jax
    t0 = time.monotonic() + 0.05
    t1 = t0 + seconds
    if mix["arrivals"] == "closed_loop":
        pool = workload.closed_pool(mix, seed, 8 + int(seconds), vocab)
        feed = Feeder(engine, pool, t0, t1, clients=int(mix["clients"]))
    else:
        items = workload.open_schedule(mix, seed, seconds, vocab)
        feed = Feeder(engine, items, t0, t1)
    before = counters(engine)
    if on_open is not None:
        on_open()
    feed.start()
    win = Window(t0, t1, feed.records, {})
    try:
        if trace_dir is not None:
            span = min(TRACE_S, seconds)
            time.sleep(max(t0 + (seconds - span) / 2 - time.monotonic(), 0.0))
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profile_options())
            ann = jax.profiler.TraceAnnotation(tracemod.WINDOW)
            ann.__enter__()
            win.trace_lo = time.monotonic()
            time.sleep(max(win.trace_lo + span - time.monotonic(), 0.0))
            win.trace_hi = time.monotonic()
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            win.trace_dir = trace_dir
        time.sleep(max(t1 - time.monotonic(), 0.0))
        after = counters(engine)
        win.counters = {k: after[k] - before[k] for k in after}
        deadline = t1 + GRACE_S
        while not feed.finished() and time.monotonic() < deadline \
                and feed.error is None:
            time.sleep(0.01)
    finally:
        feed.stop()
        abandon(engine)
    return win


def abandon(engine) -> None:
    """Cancel whatever the engine still holds (requests that outlived the
    wait), so that a later window starts from an empty engine."""
    sched = engine.scheduler
    left = list(sched.pending) + list(sched.active.values())
    for req in left:
        req.cancelled = True
    if left:
        engine.step()


# ---- correctness --------------------------------------------------------------

def sample(recs: Sequence[Dict[str, Any]], seed: int) -> List[Dict[str, Any]]:
    """The request with the most served tokens, then others drawn from the
    seed until ``CHECK_TOKENS`` served tokens of ``CHECK_MIN`` requests, or
    ``CHECK_REQUESTS``."""
    done = [r for r in recs if records.ok(r)]
    if not done:
        return []
    first = max(range(len(done)), key=lambda i: (len(done[i]["tokens"]),
                                                 len(done[i]["prompt"])))
    order = [first] + [int(i) for i in np.random.default_rng([seed, 3])
                       .permutation(len(done)) if i != first]
    out, n = [], 0
    for i in order:
        if (n >= CHECK_TOKENS and len(out) >= CHECK_MIN) \
                or len(out) >= CHECK_REQUESTS:
            break
        out.append(done[i])
        n += len(done[i]["tokens"])
    return out


def gap_readings(ref, params, model: Dict[str, Any],
                 picked: Sequence[Dict[str, Any]],
                 control: bool = False) -> Dict[str, float]:
    """Gaps by which a served token's logit lies below the reference's
    best, over ``picked``: the widest (``logit_gap``), the mean
    (``mean_gap``) and the share of tokens that are not the reference's
    first (``mismatch``).  With ``control``, the same of the tokens the int8
    control puts first, under ``control_`` names."""
    cache: Dict = {}
    gaps: Dict[str, List[np.ndarray]] = {"gap": [], "control_gap": []}
    for r in picked:
        g = common.served_gaps(ref, params, model, r["prompt"], r["tokens"],
                               control=control, fn_cache=cache)
        for k, v in g.items():
            gaps[k].append(v)
    out = {"tokens_compared": float(sum(len(r["tokens"]) for r in picked))}
    for k, pre in (("gap", ""), ("control_gap", "control_")):
        if gaps[k]:
            v = np.concatenate(gaps[k])
            out.update({pre + "logit_gap": float(v.max()),
                        pre + "mean_gap": float(v.mean()),
                        pre + "mismatch": float(np.mean(v > 0))})
    return out


def compare(ref, params, model: Dict[str, Any],
            picked: Sequence[Dict[str, Any]]) -> Dict[str, float]:
    """The readings of the served tokens that the limits judge."""
    return gap_readings(ref, params, model, picked)


def control_compare(ref, params, model: Dict[str, Any],
                    picked: Sequence[Dict[str, Any]]) -> Dict[str, float]:
    """:func:`compare` with the int8 control in the program's place: the
    gaps of the tokens the control puts first, under the names the limits
    judge.  Put in place of ``compare``, a run must come out not correct."""
    out = gap_readings(ref, params, model, picked, control=True)
    return {"tokens_compared": out["tokens_compared"],
            **{k: out["control_" + k]
               for k in ("logit_gap", "mean_gap", "mismatch")}}


def stream_checks(recs: Sequence[Dict[str, Any]], vocab: int) -> Dict[str, int]:
    """Requests of the window that never finished (cancelled once the wait
    ran out), that finished short of their tokens, and tokens outside the
    (padded) vocabulary."""
    never = sum(1 for r in recs if r["status"] in (None, "cancelled"))
    short = sum(1 for r in recs if r["status"] not in (None, "cancelled")
                and not records.ok(r))
    bad = sum(1 for r in recs for t in r["tokens"] if not 0 <= t < vocab)
    return {"never_came": never, "short_streams": short,
            "tokens_outside_vocab": bad}


# ---- one run -------------------------------------------------------------------

def run(cell_name: str, seed: int, seconds: float, trace: bool,
        root: Path = ROOT, log=print) -> Dict[str, Any]:
    """One run of ``cell_name``; returns the result object."""
    started = process_start()
    cell = find_cell(load_benchmark(root), cell_name, root)
    import jax
    dev = require_chips(cell.chips)
    log(f"compile cache {enable_compile_cache()}; device {dev.platform} "
        f"{dev.device_kind} x{len(jax.devices())}")
    config, mix = cell.config, cell.mix
    model = config["model"]
    vocab = int(model["vocab_size"])
    ref, params = make_weights(config, seed, root)
    engine, events = build_engine(config, params, annotate=trace)
    n_warm = warm_up(engine, mix, vocab, seed)
    log(f"set-up: weights and {n_warm} warm-up requests "
        f"{time.time() - started:.2f} s after process start")
    opened: Dict[str, float] = {}
    trace_dir = None
    if trace:
        trace_dir = str(root / "bench_out" / "trace" / f"{cell_name}-{seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = drive(engine, mix, seed, seconds, vocab, trace_dir,
                on_open=lambda: opened.setdefault("t", time.time()))
    setup_s = opened["t"] - started
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    engine._state = None
    del engine
    gc.collect()
    late = [r["sent"] - r["due"] for r in win.records
            if r["sent"] is not None and mix["arrivals"] != "closed_loop"]
    if late:
        log(f"generator lateness over {len(late)} requests: median "
            f"{np.median(late) * 1e3:.3f} ms, max {max(late) * 1e3:.3f} ms")
    picked = sample(win.records, seed)
    checks = stream_checks(win.records, cost.padded_vocab(model))
    cmp_ = compare(ref, params, model, picked) if picked else {}
    log(f"check: {len(picked)} requests, "
        f"{cmp_.get('tokens_compared', 0):.0f} served tokens compared with "
        f"the reference: " + ", ".join(f"{k} {v}" for k, v in cmp_.items()))
    limits: Dict[str, float] = {k: 0 for k in checks}
    limits.update(config["limits"])
    values = {**checks, **{k: cmp_.get(k) for k in config["limits"]}}
    correct = bool(picked) and all(values[k] is not None
                                   and values[k] <= limits[k] for k in limits)
    rec = {"cell": cell_name, "seconds": seconds, "setup_s": setup_s,
           "window": (win.t0, win.t1), "requests": win.records,
           "counters": win.counters, "events": events, "model": model,
           "peaks": peaks(dev.device_kind),
           "trace": None, "trace_window": (win.trace_lo, win.trace_hi)}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out: Dict[str, Any] = {"correct": correct, "attempted": len(win.records),
                           "failed": sum(1 for r in win.records
                                         if not records.ok(r))}
    if trace:
        path = tracemod.find(win.trace_dir)
        red = tracemod.reduce(tracemod.load(path, ENGINE_SPANS))
        shutil.rmtree(win.trace_dir, ignore_errors=True)
        rec["trace"] = red
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        metrics = cell.per_layer
    else:
        metrics = cell.end_to_end
    out["metrics"] = {}
    for m in metrics:
        v = reader(m["name"])(rec)
        if v is not None:
            out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    out["device"] = device
    if trace:
        out["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                            "idle_gaps": rec["trace"]["idle_gaps"]}
    out["checks"] = {k: {"value": values[k], "limit": limits[k]}
                     for k in limits}
    return out
