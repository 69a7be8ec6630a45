"""Distributed extension scans over the production mesh.

Sharding layout (see launch/mesh.py):

* DB token tensor [G, T, 6] - sequences sharded over ("pod","data")
  (disjoint gid ranges per shard), tokens sharded over "model" (the match
  compute is embarrassingly parallel over tokens).
* embeddings [E, ...]       - co-sharded with their gid's DB shard.
* output: a replicated candidate table (uniq signatures [k] + distinct-gid
  supports [k]).

Collective schedule (the whole cross-device traffic of one scan):

1. all_gather of the int32 signature matrix over "model" - brings each
   data shard's full [E_loc, T] signature matrix together (the matrix is
   ~NV+NI times smaller than the match compute, so sharding compute over
   "model" and gathering results is a bandwidth win).
2. local sort + segment reduction -> per-shard (sig, count) table, exact
   because gid ranges are disjoint.
3. all_gather of the [k,2] tables over ("pod","data") + a local
   merge-by-signature.  At 512 chips this is k*512*8B ~ 16 MB, amortized
   over E_loc*T match work: the mining step stays compute-bound, which is
   why the reverse-search design scales to O(1000) nodes.

Straggler note: the driver issues embedding batches in fixed-size chunks;
a chunk not acknowledged within a deadline is reassigned (supports are
idempotent set-unions, so duplicated work is harmless).  Elasticity:
resharding the DB is a pure gid-hash repartition of ``tokens``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .encoding import INVALID_SIG
from .engine import match_signatures_ref


def _dedup_pairs(flat_sig, flat_gid, kp: int):
    """Unique (sig, gid) pairs, fixed size kp (pads sig=-1, gid=-1)."""
    order = jnp.lexsort((flat_gid, flat_sig))
    ss, gg = flat_sig[order], flat_gid[order]
    prev_s = jnp.concatenate([jnp.full((1,), -7, ss.dtype), ss[:-1]])
    prev_g = jnp.concatenate([jnp.full((1,), -7, gg.dtype), gg[:-1]])
    keep = ((ss != prev_s) | (gg != prev_g)) & (ss >= 0)
    # stable compaction into kp slots + one dump slot for drops/overflow
    pos = jnp.cumsum(keep) - 1
    idx = jnp.where(keep & (pos < kp), pos, kp)
    out_s = jnp.full((kp + 1,), INVALID_SIG, ss.dtype)
    out_g = jnp.full((kp + 1,), -1, gg.dtype)
    out_s = out_s.at[idx].set(jnp.where(keep, ss, INVALID_SIG))
    out_g = out_g.at[idx].set(jnp.where(keep, gg, -1))
    n_pairs = keep.sum()  # caller checks n_pairs <= kp (else re-run)
    return out_s[:kp], out_g[:kp], n_pairs


def _local_candidate_table(sigs, gid_global, k: int):
    """Exact per-shard (sig -> distinct-gid count) via sort + segments."""
    E, T = sigs.shape
    flat_sig = sigs.reshape(-1)
    flat_gid = jnp.broadcast_to(gid_global[:, None], (E, T)).reshape(-1)
    order = jnp.lexsort((flat_gid, flat_sig))
    ss, gg = flat_sig[order], flat_gid[order]
    prev_s = jnp.concatenate([jnp.full((1,), -7, ss.dtype), ss[:-1]])
    prev_g = jnp.concatenate([jnp.full((1,), -7, gg.dtype), gg[:-1]])
    contrib = ((ss != prev_s) | (gg != prev_g)) & (ss >= 0)
    n_distinct = ((ss != prev_s) & (ss >= 0)).sum()
    uniq, inv = jnp.unique(ss, size=k, fill_value=INVALID_SIG,
                           return_inverse=True)
    counts = jax.ops.segment_sum(contrib.astype(jnp.int32), inv,
                                 num_segments=k)
    counts = jnp.where(uniq >= 0, counts, 0)
    return uniq, counts, n_distinct


def _flat_candidate_table(flat_sig, flat_gid, k: int):
    """(sig -> distinct-gid count) over flat pair arrays (may contain
    duplicate pairs, e.g. after a cross-token-shard merge)."""
    order = jnp.lexsort((flat_gid, flat_sig))
    ss, gg = flat_sig[order], flat_gid[order]
    prev_s = jnp.concatenate([jnp.full((1,), -7, ss.dtype), ss[:-1]])
    prev_g = jnp.concatenate([jnp.full((1,), -7, gg.dtype), gg[:-1]])
    contrib = ((ss != prev_s) | (gg != prev_g)) & (ss >= 0) & (gg >= 0)
    n_distinct = ((ss != prev_s) & (ss >= 0)).sum()
    uniq, inv = jnp.unique(ss, size=k, fill_value=INVALID_SIG,
                           return_inverse=True)
    counts = jax.ops.segment_sum(contrib.astype(jnp.int32), inv,
                                 num_segments=k)
    counts = jnp.where(uniq >= 0, counts, 0)
    return uniq, counts, n_distinct


def _merge_tables(sig_tables, cnt_tables, k: int):
    """[S,k] tables -> merged [k] table (counts add: disjoint gids)."""
    allsig = sig_tables.reshape(-1)
    allcnt = cnt_tables.reshape(-1)
    uniq, inv = jnp.unique(allsig, size=k, fill_value=INVALID_SIG,
                           return_inverse=True)
    counts = jax.ops.segment_sum(allcnt, inv, num_segments=k)
    counts = jnp.where(uniq >= 0, counts, 0)
    return uniq, counts


def make_mining_step(
    mesh: Mesh,
    k: int = 4096,
    db_axes: Tuple[str, ...] = ("data",),
    tok_axis: str = "model",
    prededup: bool = True,
):
    """Build the jitted, shard_mapped extension-scan step.

    Returns ``step(tokens, gid, phi, psi, valid, existing, nv, n_pat,
    mode) -> (uniq [k], counts [k], n_distinct)`` with a replicated output
    table.  ``gid`` must hold *local* indices into the caller's DB shard.

    ``prededup=True`` dedups (sig, gid) pairs per token shard *before* the
    "model"-axis gather: collective bytes drop from E*T*4 to k*8 per shard
    (the §Perf/mining hillclimb; False keeps the measured baseline).
    """
    n_db_shards = int(np.prod([mesh.shape[a] for a in db_axes]))

    def local_step(tokens, gid, phi, psi, valid, existing, nv, n_pat, mode):
        sigs = match_signatures_ref(
            tokens, gid, phi, psi, valid, existing, nv, n_pat, mode
        )
        # global gid offset for this data shard
        shard = jax.lax.axis_index(db_axes[0])
        for a in db_axes[1:]:
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        g_loc = tokens.shape[0]
        gid_global = gid + shard * g_loc

        if prededup:
            # 1) dedup local pairs, gather only the k-sized pair tables
            E, T = sigs.shape
            flat_sig = sigs.reshape(-1)
            flat_gid = jnp.broadcast_to(
                gid_global[:, None], (E, T)).reshape(-1)
            ps, pg, _ = _dedup_pairs(flat_sig, flat_gid, k)
            all_s = jax.lax.all_gather(ps, tok_axis).reshape(-1)
            all_g = jax.lax.all_gather(pg, tok_axis).reshape(-1)
            sigs2, gids2 = all_s, all_g  # may contain cross-shard dups
            uniq, counts, n_distinct = _flat_candidate_table(
                sigs2, gids2, k)
        else:
            # 1) reassemble each data shard's full signature matrix
            sigs = jax.lax.all_gather(sigs, tok_axis, axis=1, tiled=True)
            uniq, counts, n_distinct = _local_candidate_table(
                sigs, gid_global, k)
        # 2) merge candidate tables across DB shards
        uniq_all = jax.lax.all_gather(uniq, db_axes, tiled=False)
        cnt_all = jax.lax.all_gather(counts, db_axes, tiled=False)
        uniq, counts = _merge_tables(uniq_all, cnt_all, k)
        n_distinct = jax.lax.pmax(n_distinct, db_axes)
        return uniq, counts, n_distinct

    db_dim = tuple(db_axes) if len(db_axes) > 1 else db_axes[0]
    specs_in = (
        P(db_dim, tok_axis, None),  # tokens
        P(db_dim),                  # gid (local indices)
        P(db_dim, None),            # phi
        P(db_dim, None),            # psi
        P(db_dim),                  # valid
        P(),                        # existing
        P(), P(), P(),              # nv, n_pat, mode
    )
    step = jax.shard_map(
        local_step, mesh=mesh, in_specs=specs_in,
        out_specs=(P(), P(), P()), check_vma=False,
    )
    return jax.jit(step)
