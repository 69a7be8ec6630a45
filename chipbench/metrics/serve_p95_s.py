"""95th percentile, over every request due in the window, of the host-
clock time from its due time to its exact answer; a failed, inexact or
never-answered request counts as infinite."""
from chipbench.lib.traffic import percentile


def read(rec):
    lat = rec.get("latency_s")
    return percentile(lat, 95) if lat else None
