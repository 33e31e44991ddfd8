"""itl_p50_s: median (nearest rank) over every gap between two tokens of
every request of the window, stamped as the engine emitted them."""

from bench import records
from bench.stats import nearest_rank


def read(rec):
    gaps = [g for r in rec["requests"] for g in records.gaps(r)]
    return nearest_rank(gaps, 0.5) if gaps else None
