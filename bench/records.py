"""Per-request records of a window, as the feeder fills them.

A record holds the request (prompt, tokens asked for), when it was due,
when the feeder handed it to the engine (``sent``), every token with the
host-clock time (``time.monotonic``) at which the engine emitted it, and
the engine's terminal status and own ``ttft_s``.  Open-loop requests are
due on their schedule; a closed-loop request is due when its client's
previous reply finished.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence


def new_record(prompt: Sequence[int], max_new_tokens: int,
               due: float) -> Dict[str, Any]:
    return {"due": due, "sent": None, "prompt": list(prompt),
            "max_new_tokens": int(max_new_tokens), "tokens": [],
            "times": [], "status": None, "engine_ttft_s": None}


def ok(rec: Dict[str, Any]) -> bool:
    """The request completed with every token asked for."""
    return (rec["status"] == "completed"
            and len(rec["tokens"]) == rec["max_new_tokens"])


def ttft(rec: Dict[str, Any]) -> Optional[float]:
    return rec["times"][0] - rec["due"] if rec["times"] else None


def gaps(rec: Dict[str, Any]) -> List[float]:
    t = rec["times"]
    return [b - a for a, b in zip(t, t[1:])]
