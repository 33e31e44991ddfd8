"""queue_wait_mean_s (layer: scheduler, serve/scheduler.py): mean time a
request of the window waited in the queue before admission, from the
engine's ``serve_queue_wait_seconds`` histogram (sum over count)."""


def read(rec):
    c = rec["counters"]
    n = c.get("queue_wait_count", 0)
    return c["queue_wait_sum"] / n if n else None
