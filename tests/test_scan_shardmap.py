"""Repro for the lax.scan-inside-shard_map miscompile seen on jax 0.4.

``repro.serving.batch`` unrolls its step loop because, on the jax 0.4
CPU backend, the scan + shard_map combination dropped matches
(containment came out *lower* on non-zero data/model shards; the same
scan unsharded and the same shard_map unrolled both agreed with the
oracle).  On the installed jax the two agree; this test keeps checking
that, so a future change of the loop to ``lax.scan`` rests on it.

The repro runs in a subprocess so the 8-fake-device XLA_FLAGS override
cannot leak into the suite's single-device processes.
"""
import os
import subprocess
import sys

import pytest

REPRO_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src"); sys.path.insert(0, "tests")
import functools
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from conftest import random_db
from repro.mining.driver import AcceleratedMiner
from repro.mining.encoding import encode_db, PAD_PHI, PAD_PSI
from repro.serving.bank import compile_bank
from repro.serving.batch import (
    _step_once, build_token_index, max_key_bucket,
)

db = random_db(3, n_seq=8, n_steps=4, n_v=4)
bank = compile_bank(
    AcceleratedMiner(db).mine_rs(2, max_len=4), pad_patterns_to=16
)
tdb = encode_db(db)
tok = jnp.asarray(tdb.tokens)
tmax = max_key_bucket(tdb.tokens, bank.n_label_keys)
E = 8


def dense_join(tokens, steps, pvalid, *, scan):
    # the flat embedding join with an E-padded root frontier so the
    # scan carry has a uniform shape (only row 0 starts valid; padding
    # rows never produce candidates, so this is equivalent to the
    # production 1-row root frontier)
    B = tokens.shape[0]
    Pn, L, F = steps.shape
    order, start, count = build_token_index(
        tokens, n_label_keys=bank.n_label_keys
    )
    cell_b = jnp.repeat(jnp.arange(B, dtype=jnp.int32), Pn)
    cell_steps = jnp.broadcast_to(
        steps[None], (B,) + steps.shape
    ).reshape(B * Pn, L, F)
    N = B * Pn
    phi = jnp.full((N, E, L), PAD_PHI, jnp.int32)
    psi = jnp.full((N, E, bank.nv), PAD_PSI, jnp.int32)
    valid = jnp.broadcast_to(jnp.arange(E)[None, :] < 1, (N, E))
    ovf = jnp.zeros((N,), bool)

    def body(state, step_k):
        phi, psi, valid, ovf = state
        pn, sn, vn, on = _step_once(
            tokens, order, start, count, cell_b, step_k,
            phi, psi, valid, emax=E, tmax=tmax,
            use_kernel=False, block_g=64, uniform=False, compact=True,
        )
        alive = step_k[:, 6] > 0
        phi = jnp.where(alive[:, None, None], pn, phi)
        psi = jnp.where(alive[:, None, None], sn, psi)
        valid = jnp.where(alive[:, None], vn, valid)
        ovf = jnp.where(alive, on | ovf, ovf)
        return (phi, psi, valid, ovf), None

    xs = jnp.swapaxes(cell_steps, 0, 1)  # [L, N, F]
    state = (phi, psi, valid, ovf)
    if scan:
        state, _ = lax.scan(body, state, xs)
    else:
        for k in range(L):
            state, _ = body(state, xs[k])
    _, _, valid, ovf = state
    real = (pvalid > 0)[None, :]
    return (valid.any(-1).reshape(B, Pn) & real,
            ovf.reshape(B, Pn) & real)


mesh = jax.make_mesh((4, 2), ("data", "model"))
specs_in = (P("data", None, None), P("model", None, None), P("model"))
specs_out = (P("data", "model"), P("data", "model"))
args = (tok, jnp.asarray(bank.steps), jnp.asarray(bank.pattern_valid))
got = {}
for scan in (False, True):
    f = jax.shard_map(
        functools.partial(dense_join, scan=scan), mesh=mesh,
        in_specs=specs_in, out_specs=specs_out, check_vma=False,
    )
    c, o = jax.jit(f)(*args)
    got[scan] = np.asarray(c)
# sanity: the unsharded scan agrees with the unsharded unrolled loop,
# pinning any mismatch below on the scan + shard_map combination
cu, _ = dense_join(*args, scan=False)
cs, _ = dense_join(*args, scan=True)
assert np.array_equal(np.asarray(cu), np.asarray(cs)), \
    "unsharded scan != unrolled: repro assumptions broken"
assert got[False].sum() > 0, "degenerate repro: nothing contained"
if np.array_equal(got[True], got[False]):
    print("SCAN-SHARDMAP-OK", int(got[True].sum()))
else:
    print("SCAN-SHARDMAP-MISMATCH",
          int(got[True].sum()), "vs", int(got[False].sum()))
"""


@pytest.mark.slow
def test_scan_inside_shard_map_matches_unrolled():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", REPRO_SCRIPT],
        capture_output=True, text=True, timeout=600,
        cwd=os.path.join(os.path.dirname(__file__), ".."), env=env,
    )
    assert "SCAN-SHARDMAP-OK" in r.stdout, (
        "lax.scan inside shard_map still miscompiles on this jax - "
        "keep the unrolled loops in serving/batch.py\n"
        + r.stdout + "\n" + r.stderr
    )
