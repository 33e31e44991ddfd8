"""The model work the engine did, rebuilt from its trace events.

The engine emits (``repro.obs`` tracer): ``prefill`` spans with
``prompt_len`` per admitted request, ``decode_step`` spans per batched step,
and ``request_finished`` events.  A request occupies its slot from the end
of its ``prefill`` span until its ``request_finished`` event; in its
``j``-th batched step it attends ``prompt_len + j + 1`` positions (its cache
and the token it feeds).  Times are the engine clock's (``time.monotonic``).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple


def work(events: Sequence[Dict], lo: float, hi: float) -> Dict[str, List]:
    """Prompt lengths of the prefills, and per batched decode step the
    positions each active slot attends, for the spans that start in
    ``[lo, hi)``."""
    ready: Dict[int, Tuple[float, int]] = {}
    finish: Dict[int, float] = {}
    prefills, steps = [], []
    for ev in events:
        if ev.get("name") == "prefill" and ev.get("kind") == "span":
            ready[ev["uid"]] = (ev["t"] + ev["dur_s"], int(ev["prompt_len"]))
            if lo <= ev["t"] < hi:
                prefills.append(int(ev["prompt_len"]))
        elif ev.get("name") == "request_finished":
            finish[ev["uid"]] = ev["t"]
        elif ev.get("name") == "decode_step" and ev.get("kind") == "span":
            steps.append(ev)
    done: Dict[int, int] = {}
    keys = []
    for st in sorted(steps, key=lambda e: e["t"]):
        k = []
        for uid, (t_ready, plen) in ready.items():
            if t_ready <= st["t"] < finish.get(uid, float("inf")):
                j = done.get(uid, 0)
                done[uid] = j + 1
                k.append(plen + j + 1)
        if lo <= st["t"] < hi:
            keys.append(k)
    return {"prefills": prefills, "decode_keys": keys}


def spans(events: Sequence[Dict], name: str, lo: float,
          hi: float) -> List[Dict]:
    return [ev for ev in events if ev.get("kind") == "span"
            and ev.get("name") == name and lo <= ev["t"] < hi]
