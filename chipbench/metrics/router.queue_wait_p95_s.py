"""95th percentile of the host-clock time from a request's due time to
the launch of the router batch that carries it."""
from chipbench.lib.traffic import percentile


def read(rec):
    w = rec.get("queue_wait_s")
    return percentile(w, 95) if w else None
