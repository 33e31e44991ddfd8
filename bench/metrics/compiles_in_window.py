"""compiles_in_window (layer: engine (jit), repro.obs.compiles): programs
lowered during engine steps inside the window, from the engine's
``jit_compile`` events; 0 where none was.  None where the program emits
no ``engine_step`` span, as it then reports no compiles either."""


def read(rec):
    events = rec["events"]
    if not any(e.get("name") == "engine_step" for e in events):
        return None
    lo, hi = rec["window"]
    return sum(1 for e in events
               if e.get("name") == "jit_compile" and lo <= e["t"] < hi)
