"""Seconds per whole mining job: the window's host-clock time over the
jobs it completed."""


def read(rec):
    if not rec.get("jobs"):
        return None
    return rec["elapsed_s"] / rec["jobs"]
