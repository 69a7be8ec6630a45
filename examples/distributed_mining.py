"""Distributed mining on 8 virtual devices: DB sharded over a (4 data x 2
model) mesh, one extension scan via the shard_map step, verified against
the exact host path; then a checkpoint/kill/resume cycle of the full
miner (the fault-tolerance drill a real cluster job runs).

    PYTHONPATH=src python examples/distributed_mining.py
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import random  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, "src")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.compile import compile_sequence  # noqa: E402
from repro.data.synthetic import random_graph_sequence  # noqa: E402
from repro.mining.distributed import make_mining_step  # noqa: E402
from repro.mining.driver import AcceleratedMiner  # noqa: E402
from repro.mining.encoding import (  # noqa: E402
    encode_db,
    encode_embeddings,
    encode_pattern_trs,
)
from repro.mining.engine import (  # noqa: E402
    MODE_ROOT,
    aggregate_host,
    match_signatures,
)


def main():
    rng = random.Random(0)
    db = [compile_sequence(random_graph_sequence(rng, n_steps=5, n_v=5))
          for _ in range(16)]

    # ---- one sharded extension scan vs the exact single-device path
    tdb = encode_db(db, pad_to=64)
    embs = [(g, (), ()) for g in range(len(db))]
    gid, phi, psi = encode_embeddings(embs, 8, 8)
    valid = np.ones((len(embs),), np.int32)
    existing = encode_pattern_trs((), 16)
    sigs = match_signatures(
        jnp.asarray(tdb.tokens), jnp.asarray(gid), jnp.asarray(phi),
        jnp.asarray(psi), jnp.asarray(valid), jnp.asarray(existing),
        jnp.int32(0), jnp.int32(0), jnp.int32(MODE_ROOT))
    host = {s: len(g_) for s, (g_, _) in
            aggregate_host(np.asarray(sigs), gid).items()}

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    step = make_mining_step(mesh, k=1024, db_axes=("data",),
                            tok_axis="model")
    gid_local = (gid % (len(db) // 4)).astype(np.int32)
    with jax.set_mesh(mesh):
        uniq, counts, _ = step(
            jnp.asarray(tdb.tokens), jnp.asarray(gid_local),
            jnp.asarray(phi), jnp.asarray(psi), jnp.asarray(valid),
            jnp.asarray(existing),
            jnp.int32(0), jnp.int32(0), jnp.int32(MODE_ROOT))
    dev = {int(s): int(c)
           for s, c in zip(np.asarray(uniq), np.asarray(counts)) if s >= 0}
    assert dev == host
    print(f"sharded scan over {len(jax.devices())} devices == exact host "
          f"counts ({len(dev)} candidate extensions)  OK")

    # ---- fault tolerance: checkpoint, simulated crash, resume
    ck = "/tmp/repro_mine.ckpt"
    if os.path.exists(ck):
        os.unlink(ck)
    full = AcceleratedMiner(db).mine_rs(2, max_len=5)

    from repro.mining import checkpoint as ckpt
    calls = {"n": 0}
    orig = ckpt.save_state

    class Crash(Exception):
        pass

    def crashing(path, patterns, stack, meta=None):
        orig(path, patterns, stack, meta)
        calls["n"] += 1
        if calls["n"] == 2 and stack:
            raise Crash("simulated worker loss")

    ckpt.save_state = crashing
    try:
        AcceleratedMiner(db).mine_rs(2, max_len=5, checkpoint_path=ck,
                                     checkpoint_every=2)
        crashed = False
    except Crash:
        crashed = True
    finally:
        ckpt.save_state = orig
    resumed = AcceleratedMiner(db).mine_rs(2, max_len=5,
                                           checkpoint_path=ck, resume=True)
    assert resumed.patterns == full.patterns
    print(f"crash-after-checkpoint {'simulated' if crashed else '(ran out)'}"
          f", resume produced identical {len(resumed.patterns)} rFTSs  OK")


if __name__ == "__main__":
    main()
