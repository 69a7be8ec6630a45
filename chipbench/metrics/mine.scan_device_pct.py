"""Share of the traced window the device spent in the miner's scan
programs (jit names ``jit_match_signatures*``)."""


def read(rec):
    t = rec.get("trace")
    if not t or not t["window_s"]:
        return None
    s = sum(v for k, v in t["programs"].items()
            if k.startswith("jit_match_signatures"))
    return 100.0 * s / t["window_s"] if s else None
