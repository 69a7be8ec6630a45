"""Cells the server escalated to the wider device replay, per query
(program counters ``escalated_cells`` / router ``queries`` over the
window)."""


def read(rec):
    c = rec.get("counters") or {}
    q = c.get("queries", 0)
    return c.get("shards_escalated_cells", 0) / q if q else None
