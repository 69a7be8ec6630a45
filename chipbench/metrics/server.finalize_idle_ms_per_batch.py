"""Device-idle milliseconds per finalized batch: idle time under the
program's ``cluster.fence`` span (readback, escalation, host oracle,
cache fill), over the number of ``cluster.fence`` spans."""
from chipbench.lib import spans


def read(rec):
    count = (rec.get("trace") or {}).get("span_count") or {}
    return spans.idle_ms_per(rec, ("cluster.fence",),
                             count.get("cluster.fence"))
