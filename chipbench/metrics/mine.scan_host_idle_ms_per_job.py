"""Device-idle milliseconds per mining job in the host side of the
miner's scans: idle time under the program's ``mining.pack``,
``mining.scan`` and ``mining.aggregate`` spans, over the window's jobs."""
from chipbench.lib import spans


def read(rec):
    return spans.idle_ms_per(
        rec, ("mining.pack", "mining.scan", "mining.aggregate"),
        rec.get("jobs"))
