"""Cells the server settled with the host containment oracle, per query
(program counters ``host_fallback_cells`` / router ``queries`` over the
window)."""


def read(rec):
    c = rec.get("counters") or {}
    q = c.get("queries", 0)
    return c.get("shards_host_fallback_cells", 0) / q if q else None
