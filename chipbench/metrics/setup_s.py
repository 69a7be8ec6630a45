"""Set-up seconds: from the start of the process to the end of warm-up
(host clock)."""


def read(rec):
    return rec.get("setup_s")
