"""Compile events: each new lowering of a jitted program, reported as a
``jit_compile`` trace event of the engine step that caused it.

One process-wide ``jax.monitoring`` duration listener (installed once by
:func:`install`, however many engines are built) watches JAX's
``jaxpr_to_mlir_module`` events.  JAX lowers a program once per new
shape, whether or not the persistent compilation cache then supplies the
executable, so each event is a program that had not run at that shape in
this process.  The listener emits ``{"name": "jit_compile", "fn": str,
"seconds": float}`` into the tracer of the bus that :func:`attributed_to`
made current on the calling thread; a lowering on another thread, or
outside any such block, is not attributed.

These are trace events only, never registry metrics: a count of compiles
depends on what the process compiled before, and registry renders must
replay bit-identically across runs.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Iterator

__all__ = ["LOWERING_EVENT", "attributed_to", "install"]

#: The ``jax.monitoring`` duration event of one lowering (JAX 0.9); its
#: ``fun_name`` keyword names the program, e.g. ``jit(prefill)``.
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

_current = threading.local()
_lock = threading.Lock()
_installed = False


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event != LOWERING_EVENT:
        return
    bus = getattr(_current, "bus", None)
    if bus is not None:
        bus.tracer.event("jit_compile", fn=str(kwargs.get("fun_name")),
                         seconds=float(duration_secs))


def install() -> bool:
    """Register the listener with ``jax.monitoring`` unless it already is.
    Returns whether this call registered it."""
    global _installed
    with _lock:
        if _installed:
            return False
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
        return True


@contextlib.contextmanager
def attributed_to(bus) -> Iterator[None]:
    """Within the block, lowerings on this thread go to ``bus``."""
    prev = getattr(_current, "bus", None)
    _current.bus = bus
    try:
        yield
    finally:
        _current.bus = prev
