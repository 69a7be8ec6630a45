"""Kernel microbenchmarks + the fused trie-walk artifact.

Two parts:

1. the match/count hot loop - jnp reference vs the Pallas kernel
   (interpret mode; on CPU the *jnp* timing is the meaningful one, the
   kernel timing just proves the path runs), CSV rows only;
2. the fused trie-walk megakernel (``kernels.trie_walk`` behind
   ``bank_layout="trie_fused"``) vs the unrolled per-level walk, on a
   mined bank: interleaved cold rounds of the *walk itself*
   (launch + scatter, no cache/score), a device-dispatch count per
   query batch (the fused path's contract is ONE, independent of trie
   depth; the per-level path pays one per level), a full three-layout
   row-divergence count, and a measured-vs-roofline table for the fused
   dispatch from ``roofline/hlo_cost.py``'s trip-count-aware HLO walk.

   The timed regime is the *router flush*: small query chunks
   (``FLUSH_CHUNK``) with a precomputed ``SharedEncoding`` per chunk -
   exactly what ``ClusterRouter`` hands ``launch_rows`` on every async
   flush.  That is the dispatch-bound regime the fusion targets (one
   launch per flush instead of one per trie level); huge offline
   batches amortize the per-level launches and are served fine by the
   per-level layout, which stays the default.  Sharing the encoding
   keeps the common encode term out of both sides of the ratio.
   Emits ``BENCH_kernel.json`` (``--smoke``:
   ``BENCH_kernel_smoke.json``), gated by ``scripts/check_bench.py``
   (fused median >= 1.5x per-level, dispatches_per_query == 1,
   divergences == 0).  Writes go through tempfile + rename so a failed
   run never truncates the committed artifact.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.match_count.ops import match_signatures_kernel
from repro.mining.engine import match_signatures

OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernel.json")
OUT_SMOKE = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_kernel_smoke.json"
)


def _inputs(E, G, T, NI=16, NV=12, P=64, seed=0):
    rng = np.random.default_rng(seed)
    tokens = np.zeros((G, T, 6), np.int32)
    tokens[..., 0] = rng.integers(0, 6, (G, T))
    tokens[..., 1] = rng.integers(0, 16, (G, T))
    tokens[..., 2] = rng.integers(0, 16, (G, T))
    tokens[..., 3] = rng.integers(0, 5, (G, T))
    tokens[..., 4] = np.sort(rng.integers(0, 8, (G, T)), 1)
    tokens[..., 5] = 1
    gid = rng.integers(0, G, E).astype(np.int32)
    phi = np.full((E, NI), 0x3FFFFFF, np.int32)
    phi[:, 0] = rng.integers(0, 4, E)
    psi = np.full((E, NV), -2, np.int32)
    psi[:, 0] = rng.integers(0, 16, E)
    valid = np.ones(E, np.int32)
    existing = np.full((P, 5), -9, np.int32)
    return [jnp.asarray(x) for x in
            (tokens, gid, phi, psi, valid, existing)]


def _time(fn, *args, iters=20):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.tree.leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / iters


def main(csv=print):
    scal = [jnp.int32(1), jnp.int32(1), jnp.int32(2)]
    for E, G, T in [(1024, 256, 128), (4096, 1024, 128), (8192, 1024, 256)]:
        args = _inputs(E, G, T)
        t_ref = _time(lambda *a: match_signatures(*a, *scal), *args)
        pairs = E * T
        csv(
            f"kernel/match_jnp_E{E}_T{T},{t_ref*1e6:.0f},"
            f"gpairs_per_s={pairs/t_ref/1e9:.3f}"
        )
        if E <= 4096:
            t_k = _time(
                lambda *a: match_signatures_kernel(*a, *scal,
                                                   interpret=True),
                *args,
            )
            csv(
                f"kernel/match_pallas_interp_E{E}_T{T},{t_k*1e6:.0f},"
                f"gpairs_per_s={pairs/t_k/1e9:.3f}"
            )


def _count_dispatches(server_mod, names):
    """Wrap server-module device entry points with call counters;
    returns (counts, restore)."""
    counts = {n: 0 for n in names}
    saved = {n: getattr(server_mod, n) for n in names}

    def _wrap(n, real):
        def wrapper(*a, **kw):
            counts[n] += 1
            return real(*a, **kw)
        return wrapper

    for n in names:
        setattr(server_mod, n, _wrap(n, saved[n]))

    def restore():
        for n in names:
            setattr(server_mod, n, saved[n])

    return counts, restore


FLUSH_CHUNK = 4  # queries per timed flush - the router's latency regime


def _timed_walk(srv, chunks, encs, layouts_mod):
    """One cold pass of the walk alone over pre-encoded flush chunks -
    launch (fenced) + first-pass scatter, no cache, no scoring, no
    escalation resolve - the part the fused kernel replaces.  The
    per-chunk SharedEncoding mirrors ClusterRouter's flush path and
    keeps the common encode cost out of the measurement."""
    t0 = time.perf_counter()
    for seqs, enc in zip(chunks, encs):
        flight = srv.launch_rows(seqs, enc)
        layouts_mod.get_layout(flight.layout).finalize(srv, flight)
    return time.perf_counter() - t0


def _fused_roofline(fused_srv, queries):
    """Lower + compile the one fused dispatch this bank/batch shape
    issues and extract the trip-count-aware HLO cost terms
    (roofline/hlo_cost.py); pair them with the measured per-dispatch
    time.  The t_compute/t_memory bounds use the running chip's
    published peaks (roofline.analysis.PEAKS); on a device without
    them (the CPU) only the HLO counts are reported."""
    import repro.serving.server as server_mod
    from repro.roofline import analysis, hlo_cost
    from repro.serving.batch import fused_trie_walk

    captured = {}
    real = fused_trie_walk

    def capture(*a, **kw):
        captured["args"], captured["kw"] = a, kw
        return real(*a, **kw)

    server_mod.fused_trie_walk = capture
    try:
        fused_srv._cache.clear()
        fused_srv.query(queries)
    finally:
        server_mod.fused_trie_walk = real
    if "args" not in captured:
        return None
    a, kw = captured["args"], captured["kw"]
    lowered = real.lower(*a, **kw)
    compiled = lowered.compile()
    # measure the dispatch alone (args already on device, fenced)
    real(*a, **kw)[0].block_until_ready()
    t0 = time.perf_counter()
    iters = 5
    for _ in range(iters):
        acc, _ = real(*a, **kw)
    acc.block_until_ready()
    t_meas = (time.perf_counter() - t0) / iters
    kind = jax.devices()[0].device_kind
    if kind in analysis.PEAKS:
        roof = analysis.from_compiled(compiled, n_chips=1, model_flops=0.0,
                                      device_kind=kind)
        flops, byts = roof.flops_per_chip, roof.hbm_bytes_per_chip
        bounds = {"t_compute_bound_s": roof.t_compute,
                  "t_memory_bound_s": roof.t_memory,
                  "bound": roof.bottleneck}
    else:
        walked = hlo_cost.analyze(compiled.as_text())
        flops, byts, bounds = walked["flops"], walked["bytes"], {}
    n_cells = int(a[4].shape[0])
    return {
        "device_kind": kind,
        "n_cells": n_cells,
        "n_slots": int(a[5].shape[1]),
        "hlo_flops": flops,
        "hlo_bytes": byts,
        "t_measured_s": t_meas,
        **bounds,
        "achieved_gbytes_per_s": byts / t_meas / 1e9
        if t_meas > 0 else 0.0,
        "achieved_gflops_per_s": flops / t_meas / 1e9
        if t_meas > 0 else 0.0,
        "cells_per_s": n_cells / t_meas if t_meas > 0 else 0.0,
    }


def fused_main(csv=print, smoke: bool = False):
    import repro.serving.layouts as layouts_mod
    import repro.serving.server as server_mod
    from repro.data.synthetic import Table3Params, generate_table3_db
    from repro.mining.driver import AcceleratedMiner
    from repro.serving.bank import compile_bank
    from repro.serving.server import PatternServer, encode_queries
    from repro.serving.trie import build_trie, pack_subtrees

    try:
        from .bench_streaming import atomic_write_json, machine_id
    except ImportError:
        from bench_streaming import atomic_write_json, machine_id

    if smoke:
        db_size, n_queries, n_rounds = 60, 128, 2
        sigma_div, out_path = 10, OUT_SMOKE
    else:
        db_size, n_queries, n_rounds = 150, 1000, 6
        sigma_div, out_path = 15, OUT
    params = Table3Params(db_size=db_size, v_avg=5, n_interstates=3)
    db = generate_table3_db(params, seed=0)
    sigma = max(2, len(db) // sigma_div)
    bank = compile_bank(AcceleratedMiner(db).mine_rs(sigma, max_len=4))
    trie = build_trie(bank)
    pack = pack_subtrees(trie)
    queries = generate_table3_db(
        Table3Params(db_size=n_queries, v_avg=5, n_interstates=3),
        seed=1,
    )
    mb = max(16, 1 << (n_queries - 1).bit_length())
    perlevel = PatternServer(bank, max_batch=mb, bank_layout="trie",
                             trie=trie, metrics_ns="serving.trie")
    fused = PatternServer(bank, max_batch=mb, bank_layout="trie_fused",
                          trie=trie, metrics_ns="serving.fused")
    flat = PatternServer(bank, max_batch=mb, metrics_ns="serving.flat")

    # --- exactness gate + dispatch counts (one query batch each) ---
    counts, restore = _count_dispatches(server_mod, [
        "fused_trie_walk", "trie_root_advance",
        "trie_level_advance_gather",
    ])
    try:
        rows = {}
        for name, srv in (("flat", flat), ("trie", perlevel),
                          ("fused", fused)):
            rows[name] = np.stack(
                [r.contained for r in srv.query(queries)])
    finally:
        restore()
    divergences = int((rows["fused"] != rows["trie"]).sum()
                      + (rows["fused"] != rows["flat"]).sum())
    if divergences:
        raise AssertionError(
            f"fused layout diverged on {divergences} cells - the "
            "megakernel's bit-identity contract is broken"
        )
    n_batches = -(-len(queries) // mb)
    dispatches_per_query = counts["fused_trie_walk"] / n_batches
    perlevel_dispatches = (
        counts["trie_root_advance"]
        + counts["trie_level_advance_gather"]
    ) / n_batches

    # --- timed regime: router-flush chunks with a shared encoding
    # per chunk (see module docstring) ---
    chunks = [queries[i:i + FLUSH_CHUNK]
              for i in range(0, len(queries), FLUSH_CHUNK)]
    encs = [encode_queries(c, n_label_keys=bank.n_label_keys)
            for c in chunks]
    perlevel_c = PatternServer(bank, max_batch=FLUSH_CHUNK,
                               bank_layout="trie", trie=trie)
    fused_c = PatternServer(bank, max_batch=FLUSH_CHUNK,
                            bank_layout="trie_fused", trie=trie)
    # warm both jit caches so the rounds time steady-state dispatches
    _timed_walk(perlevel_c, chunks, encs, layouts_mod)
    _timed_walk(fused_c, chunks, encs, layouts_mod)

    # --- interleaved cold walk rounds (min of two per side per round,
    # adjacent in time: this box swings 2x between windows) ---
    rounds = []
    for _ in range(n_rounds):
        t_pl = min(_timed_walk(perlevel_c, chunks, encs, layouts_mod),
                   _timed_walk(perlevel_c, chunks, encs, layouts_mod))
        t_f = min(_timed_walk(fused_c, chunks, encs, layouts_mod),
                  _timed_walk(fused_c, chunks, encs, layouts_mod))
        rounds.append({
            "perlevel_walk_s": t_pl,
            "fused_walk_s": t_f,
            "speedup_fused_vs_perlevel": t_pl / t_f,
        })
    sp = sorted(r["speedup_fused_vs_perlevel"] for r in rounds)
    roof = _fused_roofline(fused, queries)
    payload = {
        "machine": machine_id(),
        "bank_patterns": bank.n_patterns,
        "trie_nodes": trie.n_nodes,
        "trie_depth": trie.depth,
        "n_subtrees": pack.n_subtrees,
        "n_slots": pack.n_slots,
        "n_queries": len(queries),
        "n_batches": n_batches,
        "flush_chunk": FLUSH_CHUNK,
        "n_flushes": len(chunks),
        "divergences": divergences,
        "dispatches_per_query": dispatches_per_query,
        "perlevel_dispatches_per_query": perlevel_dispatches,
        "speedup_fused_vs_perlevel": sp[-1],
        "speedup_fused_vs_perlevel_median": sp[len(sp) // 2],
        "rounds": rounds,
        "roofline": roof or {},
        "metrics": {**fused.metrics.snapshot(),
                    **perlevel.metrics.snapshot()},
    }
    atomic_write_json(out_path, payload)
    csv(f"kernel/fused_walk,{rounds[-1]['fused_walk_s']*1e6:.0f},"
        f"x{sp[len(sp) // 2]:.2f}_vs_perlevel")
    csv(f"kernel/fused_dispatches,{dispatches_per_query:.0f},"
        f"perlevel={perlevel_dispatches:.0f}")
    if "bound" in (roof or {}):
        csv(f"kernel/fused_roofline,{roof['t_measured_s']*1e6:.0f},"
            f"bound={roof['bound']}_"
            f"tmem={roof['t_memory_bound_s']*1e6:.1f}us")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fused-walk config writing "
                         "BENCH_kernel_smoke.json (the CI tier-2 "
                         "dispatch/divergence gate)")
    ap.add_argument("--micro", action="store_true",
                    help="also run the match/count micro rows")
    args = ap.parse_args()
    if args.micro:
        main()
    out = fused_main(smoke=args.smoke)
    print(f"# fused trie walk: x"
          f"{out['speedup_fused_vs_perlevel_median']:.2f} median vs "
          f"per-level ({out['perlevel_dispatches_per_query']:.0f} -> "
          f"{out['dispatches_per_query']:.0f} dispatches/query batch, "
          f"depth {out['trie_depth']})")
