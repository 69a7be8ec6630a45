"""Device calls per mining job (the program's
``AcceleratedMiner.n_device_calls``, summed over the window's jobs)."""


def read(rec):
    if not rec.get("jobs"):
        return None
    return rec["device_calls"] / rec["jobs"]
