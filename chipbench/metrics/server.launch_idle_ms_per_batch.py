"""Device-idle milliseconds per launched batch: idle time under the
program's ``cluster.flush`` span (shared encode, token index, prescreen,
join launches), over the number of ``cluster.flush`` spans."""
from chipbench.lib import spans


def read(rec):
    count = (rec.get("trace") or {}).get("span_count") or {}
    return spans.idle_ms_per(rec, ("cluster.flush",),
                             count.get("cluster.flush"))
