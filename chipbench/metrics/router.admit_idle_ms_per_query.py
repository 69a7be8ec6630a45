"""Device-idle milliseconds per query while the router admits it: idle
time under the program's ``cluster.submit`` span (fingerprint, cache
lookups, queueing) and not under a ``cluster.flush`` launched from it,
over the router's ``queries`` in the window."""
from chipbench.lib import spans


def read(rec):
    return spans.idle_ms_per(rec, ("cluster.submit",),
                             (rec.get("counters") or {}).get("queries"),
                             exclude=("cluster.flush",))
