"""Device-idle milliseconds per mining job in the reverse-search child
test: idle time under the program's ``mining.children`` span
(canonical grouping and the ``parent`` membership test) and not under
``mining.rebuild``, over the window's jobs."""
from chipbench.lib import spans


def read(rec):
    return spans.idle_ms_per(rec, ("mining.children",), rec.get("jobs"),
                             exclude=("mining.rebuild",))
