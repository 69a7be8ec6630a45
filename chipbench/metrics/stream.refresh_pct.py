"""Share of the window spent inside ``refresh()`` (host clock around
each call; it returns a host dict, so the call is fenced)."""


def read(rec):
    if not rec.get("elapsed_s") or "refresh_s" not in rec:
        return None
    return 100.0 * rec["refresh_s"] / rec["elapsed_s"]
