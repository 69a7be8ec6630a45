"""Arrivals covered by a completed ``refresh()``, over the window's
host-clock seconds."""


def read(rec):
    if not rec.get("arrivals"):
        return None
    return rec["arrivals"] / rec["elapsed_s"]
