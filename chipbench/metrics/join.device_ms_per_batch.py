"""Device milliseconds of the containment-join programs per device
batch: the traced device time of every program whose jit name is one of
the joins, over the server's ``device_batches`` in the window."""

JOINS = ("pair_contains_indexed", "trie_root_advance",
         "trie_level_advance_gather", "fused_trie_walk")


def read(rec):
    t = rec.get("trace")
    batches = (rec.get("counters") or {}).get("shards_device_batches", 0)
    if not t or not batches:
        return None
    s = sum(v for k, v in t["programs"].items()
            if any(k == f"jit_{j}" or k.startswith(f"jit_{j}(")
                   or k.startswith(f"jit_{j}.") for j in JOINS))
    return 1000.0 * s / batches if s else None
