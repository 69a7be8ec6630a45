"""Device-idle milliseconds per mining job in the child-embedding
rebuild: idle time under the program's ``mining.rebuild`` span, over
the window's jobs."""
from chipbench.lib import spans


def read(rec):
    return spans.idle_ms_per(rec, ("mining.rebuild",), rec.get("jobs"))
