"""Exact answers over the window's host-clock seconds, the window
running from its start until the last request due in it is answered:
all the work and all the time.  Below the knee it guards against lost
and failed work; above it, it reads the capacity."""


def read(rec):
    if "exact_answers" not in rec:
        return None
    return rec["exact_answers"] / rec["served_s"]
