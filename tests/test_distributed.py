"""Multi-device semantics of the sharded mining step, exercised on 8
virtual CPU devices in a subprocess (device count is locked at first JAX
init, so it cannot be changed inside this process)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src"); sys.path.insert(0, "tests")
import numpy as np
import jax
import jax.numpy as jnp
from conftest import random_db
from repro.mining.encoding import encode_db, encode_embeddings, encode_pattern_trs
from repro.mining.engine import MODE_ROOT, aggregate_host, match_signatures
from repro.mining.distributed import make_mining_step

db = random_db(3, n_seq=16, n_steps=5, n_v=5)
tdb = encode_db(db, pad_to=64)  # T divisible by model axis
embs = [(g, (), ()) for g in range(len(db))]
gid_g, phi, psi = encode_embeddings(embs, 8, 8)
valid = np.ones((len(embs),), np.int32)
existing = encode_pattern_trs((), 16)

# exact host reference (single device path)
sigs = match_signatures(
    jnp.asarray(tdb.tokens), jnp.asarray(gid_g), jnp.asarray(phi),
    jnp.asarray(psi), jnp.asarray(valid), jnp.asarray(existing),
    jnp.int32(0), jnp.int32(0), jnp.int32(MODE_ROOT))
host = {s: len(gs) for s, (gs, _) in aggregate_host(np.asarray(sigs), gid_g).items()}

mesh = jax.make_mesh((4, 2), ("data", "model"))
gid_local = (gid_g % (len(db) // 4)).astype(np.int32)
for prededup in (False, True):
    step = make_mining_step(mesh, k=1024, db_axes=("data",),
                            tok_axis="model", prededup=prededup)
    with jax.set_mesh(mesh):
        uniq, counts, n_distinct = step(
            jnp.asarray(tdb.tokens), jnp.asarray(gid_local), jnp.asarray(phi),
            jnp.asarray(psi), jnp.asarray(valid), jnp.asarray(existing),
            jnp.int32(0), jnp.int32(0), jnp.int32(MODE_ROOT))
    dev = {int(s): int(c) for s, c in zip(np.asarray(uniq), np.asarray(counts)) if s >= 0}
    assert int(n_distinct) <= 1024
    assert dev == host, (prededup, len(dev), len(host))
print("DISTRIBUTED-OK", len(dev))
"""


@pytest.mark.slow
@pytest.mark.subprocess
def test_sharded_mining_step_8dev():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        cwd=os.path.join(os.path.dirname(__file__), ".."), env=env,
    )
    assert "DISTRIBUTED-OK" in r.stdout, r.stdout + "\n" + r.stderr
