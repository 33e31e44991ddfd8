"""ttft_p90_s: 90th percentile (nearest rank) over the requests of the
window of the time from when a request was due to its first token."""

from bench import records
from bench.stats import nearest_rank


def read(rec):
    t = [v for v in map(records.ttft, rec["requests"]) if v is not None]
    return nearest_rank(t, 0.9) if t else None
