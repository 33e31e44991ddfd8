"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device metrics.

``load`` reads the trace with ``jax.profiler.ProfileData`` into plain
tuples; ``reduce`` works on those alone, so that it can be checked on a
hand-built trace.  Times are nanoseconds on the trace's own clock, on which
the device planes and the host's annotations are aligned by the profiler.

What is read:

* device operations: the events of each device plane's ``XLA Ops`` line;
  where a trace has no device plane (the CPU), events that carry an
  ``hlo_op`` stat;
* device programs: the events of the ``XLA Modules`` line, named
  ``jit_<function>(<fingerprint>)`` (one fingerprint per compiled shape),
  or the ``hlo_module`` stat of the operations;
* host spans: the events of the host plane whose name is one of ``spans``
  (``jax.profiler.TraceAnnotation`` puts them there);
* the window: the host event named ``window`` (else the whole trace).

``reduce`` gives the busy time (the union of operation intervals inside the
window, averaged over devices), the device time and count of each program
and of each of its compiled variants, the operations that took most time
(named by their program and their HLO instruction; loops and calls, whose
bodies' operations are listed themselves, are left out), and the longest idle
gaps, each labelled by the innermost host span that covers its midpoint.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
#: (name, start_ns, end_ns, stats)
Event = Tuple[str, float, float, Dict]

WINDOW = "bench_window"


def find(directory: str) -> str:
    """The newest ``*.xplane.pb`` under ``directory``."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def _stats(ev) -> Dict:
    try:
        return dict(ev.stats)
    except Exception:                 # a stat of a type ProfileData cannot give
        return {}


def load(path: str, spans: Iterable[str]) -> Dict[str, object]:
    """Device operations and programs per device, and host spans."""
    from jax.profiler import ProfileData

    spans = set(spans) | {WINDOW}
    pd = ProfileData.from_file(path)
    devices: List[Dict[str, List[Event]]] = []
    host: List[Event] = []
    cpu_ops: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    dev[key].append((ev.name, ev.start_ns, ev.end_ns,
                                     _stats(ev) if key == "modules" else {}))
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        host.append((ev.name, ev.start_ns, ev.end_ns, {}))
                    elif not devices:
                        st = _stats(ev)
                        if "hlo_op" in st:
                            cpu_ops.append((ev.name, ev.start_ns, ev.end_ns,
                                            st))
    if not devices and cpu_ops:
        devices.append({"ops": cpu_ops, "modules": [
            (str(st.get("hlo_module")), s, e, st)
            for _, s, e, st in cpu_ops]})
    return {"devices": devices, "host": host}


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as sorted disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def idle_gaps(busy: Sequence[Interval], lo: float,
              hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``busy`` (merged) does not cover."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(t: float, host: Sequence[Event]) -> str:
    """The innermost host span (not the window) that covers ``t``."""
    best: Optional[Event] = None
    for ev in host:
        if ev[0] != WINDOW and ev[1] <= t <= ev[2] and (
                best is None or ev[2] - ev[1] < best[2] - best[1]):
            best = ev
    return best[0] if best else "no span"


def program_name(name: str) -> str:
    """A program's name without the id XLA appends: ``jit_f(12)`` -> ``jit_f``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def op_name(name: str) -> str:
    """An HLO instruction's name, kind and result shape without its layout:
    ``%copy.4 = bf16[8,2048]{1,0:T(8,128)} copy(...)`` ->
    ``%copy.4 copy bf16[8,2048]``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    m = re.match(r"(\(.*?\)|\S+)\s+([\w-]+)", rest)
    if not m:
        return head
    return f"{head} {m.group(2)} {m.group(1)}"[:160]


def _owners(ops: Sequence[Event], modules: Sequence[Event]) -> List[str]:
    """For each operation, the program (module) running when it started."""
    mods = sorted((s, e, program_name(n)) for n, s, e, _ in modules)
    order = sorted(range(len(ops)), key=lambda i: ops[i][1])
    out = [""] * len(ops)
    j = 0
    for i in order:
        s = ops[i][1]
        while j < len(mods) and mods[j][1] <= s:
            j += 1
        if j < len(mods) and mods[j][0] <= s:
            out[i] = mods[j][2]
    return out


def reduce(trace: Dict[str, object], top: int = 10) -> Dict[str, object]:
    """Busy and window seconds, device seconds and count per program, the
    ``top`` operations by device time and the ``top`` longest idle gaps."""
    host: List[Event] = trace["host"]
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device operation")
    win = [ev for ev in host if ev[0] == WINDOW]
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        lo = min(s for d in devices for _, s, _, _ in d["ops"])
        hi = max(e for d in devices for _, _, e, _ in d["ops"])
    busy_ns, gaps = 0.0, []
    ops: Dict[str, float] = collections.defaultdict(float)
    programs: Dict[str, List[float]] = {}
    for d in devices:
        busy = clip(merge((s, e) for _, s, e, _ in d["ops"]), lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        gaps += idle_gaps(busy, lo, hi)
        owners = _owners(d["ops"], d["modules"])
        for (name, s, e, _), owner in zip(d["ops"], owners):
            short = op_name(name)
            if short.split(" ")[1:2] in (["while"], ["conditional"],
                                         ["call"]):
                continue            # a loop's body ops are listed themselves
            for cs, ce in clip([(s, e)], lo, hi):
                ops[f"{owner}: {short}" if owner else short] += ce - cs
        for name, s, e, st in d["modules"]:
            for cs, ce in clip([(s, e)], lo, hi):
                variant = name if "(" in name else "%s(%s)" % (
                    name, st.get("program_id", ""))
                for key in (program_name(name), variant):
                    acc = programs.setdefault(key, [0.0, 0])
                    acc[0] += (ce - cs) / 1e9
                    acc[1] += 1
    n = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "programs": {k: {"device_s": v[0] / n, "count": v[1] / n}
                     for k, v in programs.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label((s + e) / 2, host), (e - s) / 1e9]
                      for s, e in gaps[:top]],
    }


def program_time(reduced: Dict[str, object], prefix: str) -> Tuple[float, float]:
    """Device seconds and executions of the programs named ``prefix...``
    (``programs`` also holds each compiled variant, as ``name(id)``)."""
    t = c = 0.0
    for name, v in reduced["programs"].items():
        if name.startswith(prefix) and "(" not in name:
            t += v["device_s"]
            c += v["count"]
    return t, c


def batched_decode(reduced: Dict[str, object]) -> Tuple[float, float]:
    """Device seconds and executions of the batched decode step.  Where the
    decode program ran compiled for several batch sizes (prompts absorbed
    one token per step at batch 1), it is the variant whose executions are
    the longest on average, as it moves every slot's state."""
    variants = decode_variants(reduced)
    if not variants:
        return 0.0, 0.0
    best = max(variants, key=lambda v: v["device_s"] / v["count"])
    return best["device_s"], best["count"]


def absorb_steps(reduced: Dict[str, object]) -> Tuple[float, float]:
    """Device seconds and executions of the decode variants other than the
    batched one: batch-1 steps that absorb a prompt one token each."""
    variants = decode_variants(reduced)
    if len(variants) < 2:
        return 0.0, 0.0
    t, n = batched_decode(reduced)
    return (sum(v["device_s"] for v in variants) - t,
            sum(v["count"] for v in variants) - n)


def decode_variants(reduced: Dict[str, object]) -> List[Dict[str, float]]:
    """Device seconds and executions of each compiled decode program."""
    return [v for name, v in reduced["programs"].items()
            if name.startswith("jit_decode_step(") and v["count"]]
