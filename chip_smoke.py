"""Smoke run of the main path on TPU: mine, serve and stream once at the
paper's Table 3 defaults, each phase checked against its host oracle.

    python chip_smoke.py              # one chip: mine, serve, stream
    python chip_smoke.py --four-chip  # four one-chip hosts behind the router

One chip: ``AcceleratedMiner`` (wavefront dispatch) mines the Table 3
default DB (|DB| = 1000, seed 0) at sigma = 100, max_len 6, and must
equal ``core.reverse_search.mine_gtrace_rs``.  The mined bank serves
1000 Table 3 queries (seed 1) through ``PatternServer.join`` in every
layout, with and without the Pallas kernels; every row must equal
``core.containment.contains`` and be exact, and every kernel run must
dispatch an executable holding a ``tpu_custom_call``.  A
``StreamingBank`` seeded with the mined DB as its 1000-sequence window
then observes 200 arrivals (seed 2) in batches of 25, refreshing every
4 batches; after the last refresh its frequent map must equal a batch
re-mine of the window.

``--four-chip`` runs only the cluster path: a ``ServingCluster`` of four
hosts, one per chip, in every layout, whose routed rows must equal a
single-host ``PatternServer``'s and the oracle's, with each host's
tables on its own chip.

Earlier lines report each phase's wall time, compile time and
escalation / host-fallback counts.  Any mismatch or error exits
non-zero; so does a run that finds no TPU.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.containment import contains  # noqa: E402
from repro.core.reverse_search import mine_gtrace_rs  # noqa: E402
from repro.data.synthetic import Table3Params, generate_table3_db  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.mining.driver import AcceleratedMiner  # noqa: E402
from repro.serving import server as server_mod  # noqa: E402
from repro.serving.bank import compile_bank  # noqa: E402
from repro.serving.cluster import ServingCluster  # noqa: E402
from repro.serving.join import JoinRequest  # noqa: E402
from repro.serving.server import PatternServer  # noqa: E402
from repro.serving.streaming import StreamingBank  # noqa: E402
from repro.serving.trie import build_trie  # noqa: E402

SIGMA = 100          # 10% of |DB| = 1000, the centre of Table 4's sweep
MAX_LEN = 6
WINDOW = 1000
N_ARRIVALS = 200
STREAM_BATCH = 25
REFRESH_EVERY = 4
LAYOUTS = ("flat", "trie", "trie_fused")
# the jitted joins PatternServer dispatches; with use_kernel=True each
# one runs a Pallas kernel
KERNEL_JOINS = ("pair_contains_indexed", "trie_root_advance",
                "trie_level_advance_gather", "fused_trie_walk")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Failure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Failure(what)


class CompileClock:
    """Backend compile time and persistent-cache hits, summed from JAX's
    monitoring events (a cache hit's retrieval counts as compile
    time)."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        c0, n0, h0 = self.seconds, self.compiles, self.cache_hits
        yield
        print(f"[{name}] wall {time.perf_counter() - t0:.3f}s, compile "
              f"{self.seconds - c0:.3f}s ({self.compiles - n0} compiles, "
              f"{self.cache_hits - h0} cache hits)", flush=True)


def _shape(x):
    """``int32[4, 8]`` for an array, the value itself otherwise."""
    if not hasattr(x, "shape"):
        return x
    return f"{x.dtype}[{', '.join(map(str, x.shape))}]"


@contextlib.contextmanager
def record_joins():
    """Record every kernel-enabled join the server dispatches: per
    function, the number of distinct argument signatures and the
    largest call (by argument elements)."""
    seen = {}
    real = {n: getattr(server_mod, n) for n in KERNEL_JOINS}

    def wrap(name, fn):
        def call(*a, **kw):
            if kw.get("use_kernel"):
                static = {k: v for k, v in kw.items()
                          if isinstance(v, (bool, int))}
                sig = json.dumps([[_shape(x) for x in a], static])
                size = sum(int(np.prod(x.shape)) for x in a
                           if hasattr(x, "shape"))
                rec = seen.setdefault(name, {"sigs": set(), "size": -1})
                rec["sigs"].add(sig)
                if size > rec["size"]:
                    rec.update(size=size, sig=sig, call=(fn, a, kw))
            return fn(*a, **kw)
        return call

    for n, fn in real.items():
        setattr(server_mod, n, wrap(n, fn))
    try:
        yield seen
    finally:
        for n, fn in real.items():
            setattr(server_mod, n, fn)


def oracle_rows(bank, queries) -> np.ndarray:
    return np.array([[contains(p, q) for p in bank.patterns]
                     for q in queries], bool).reshape(
                         len(queries), bank.n_patterns)


def mine_phase(clock, db):
    with clock.phase("mine"):
        miner = AcceleratedMiner(db)
        res = miner.mine_rs(SIGMA, max_len=MAX_LEN)
    t0 = time.perf_counter()
    want = mine_gtrace_rs(db, SIGMA, max_len=MAX_LEN).patterns
    check(res.patterns == want, "mined set != mine_gtrace_rs")
    print(f"[mine] {len(res.patterns)} rFTSs with supports == "
          f"mine_gtrace_rs (oracle {time.perf_counter() - t0:.3f}s); "
          f"{miner.n_device_calls} device calls, token table "
          f"{_shape(miner.tokens)}, e_batch {miner.e_batch}, "
          f"ni {miner.ni}, nv {miner.nv}", flush=True)
    return res


def serve_phase(clock, bank, trie, queries, want):
    req = JoinRequest(seqs=tuple(queries))
    for use_kernel in (False, True):
        for layout in LAYOUTS:
            name = f"serve {layout} use_kernel={use_kernel}"
            with record_joins() as seen, clock.phase(name):
                srv = PatternServer(bank, bank_layout=layout, trie=trie,
                                    use_kernel=use_kernel)
                got = srv.join(req)
            check(got.exact, f"{name}: an answer is not exact")
            check(np.array_equal(got.rows, want),
                  f"{name}: rows != core.containment")
            print(f"[{name}] {len(queries)} rows == core.containment, "
                  f"all exact; escalated_cells "
                  f"{srv.stats['escalated_cells']}, host_fallback_cells "
                  f"{srv.stats['host_fallback_cells']}", flush=True)
            if use_kernel:
                check_kernels(name, seen)


def check_kernels(name, seen):
    check(bool(seen), f"{name}: no kernel join was dispatched")
    for fn_name, rec in sorted(seen.items()):
        print(f"[shapes] {fn_name}: {len(rec['sigs'])} signatures, "
              f"largest {rec['sig']}")
        fn, a, kw = rec["call"]
        text = fn.lower(*a, **kw).compile().as_text()
        check("tpu_custom_call" in text,
              f"{name}: {fn_name} executable has no tpu_custom_call")
        print(f"[{name}] {fn_name} executable holds tpu_custom_call",
              flush=True)


def stream_phase(clock, db):
    arrivals = generate_table3_db(Table3Params(db_size=N_ARRIVALS), seed=2)
    with clock.phase("stream"):
        sb = StreamingBank.from_db(db, minsup=SIGMA, window=WINDOW,
                                   max_len=MAX_LEN)
        for b, i in enumerate(range(0, N_ARRIVALS, STREAM_BATCH)):
            sb.observe(arrivals[i:i + STREAM_BATCH])
            if (b + 1) % REFRESH_EVERY == 0:
                sb.refresh()
        got = sb.refresh()
    window = (list(db) + list(arrivals))[-WINDOW:]
    check(sb.window_seqs == window, "stream: window != last arrivals")
    t0 = time.perf_counter()
    want = mine_gtrace_rs(window, SIGMA, max_len=MAX_LEN).patterns
    check(got == want, "stream: frequent map != batch re-mine")
    print(f"[stream] {len(got)} frequent == mine_gtrace_rs over the "
          f"final window (oracle {time.perf_counter() - t0:.3f}s); "
          f"stats {dict(sb.stats)}", flush=True)


def one_chip(clock):
    db = generate_table3_db(Table3Params(), seed=0)
    res = mine_phase(clock, db)
    bank = compile_bank(res)
    trie = build_trie(bank)
    print(f"[serve] bank {bank.n_patterns} patterns, trie "
          f"{trie.n_nodes} nodes, depth {trie.depth}", flush=True)
    queries = generate_table3_db(Table3Params(), seed=1)
    t0 = time.perf_counter()
    want = oracle_rows(bank, queries)
    print(f"[serve] oracle {time.perf_counter() - t0:.3f}s, "
          f"{int(want.sum())} contained pairs", flush=True)
    serve_phase(clock, bank, trie, queries, want)
    stream_phase(clock, db)


def four_chip(clock, devices):
    """Four one-chip hosts behind the router.  The bank comes from the
    host miner: this path serves, it does not mine.  Every layout must
    answer the same rows, so one single-host server (flat, on the
    first chip) is the reference for all three.  The reference and the
    three clusters run in concurrent threads: each host compiles its
    own programs for its own chip, and compiles in different threads
    overlap."""
    db = generate_table3_db(Table3Params(), seed=0)
    bank = compile_bank(mine_gtrace_rs(db, SIGMA, max_len=MAX_LEN))
    trie = build_trie(bank)
    queries = generate_table3_db(Table3Params(), seed=1)
    want = oracle_rows(bank, queries)
    print(f"[cluster] bank {bank.n_patterns} patterns, trie "
          f"{trie.n_nodes} nodes; {int(want.sum())} contained pairs",
          flush=True)
    req = JoinRequest(seqs=tuple(queries))

    def timed(fn, *args):
        t0 = time.perf_counter()
        return fn(*args), time.perf_counter() - t0

    def routed(layout):
        cl = ServingCluster(bank, 4, bank_layout=layout, trie=trie,
                            devices=devices)
        return cl, cl.join(req)

    with clock.phase("single flat + 3 clusters, concurrent"), \
            ThreadPoolExecutor(1 + len(LAYOUTS)) as pool:
        single = pool.submit(timed, PatternServer(bank).join, req)
        clusters = {layout: pool.submit(timed, routed, layout)
                    for layout in LAYOUTS}
        ref, dt = single.result()
        done = {layout: f.result() for layout, f in clusters.items()}
    check(ref.exact and np.array_equal(ref.rows, want),
          "single flat: rows != core.containment")
    print(f"[single flat] wall {dt:.3f}s; {len(queries)} rows == "
          f"core.containment, all exact", flush=True)
    for layout, ((cl, got), dt) in done.items():
        name = f"cluster {layout} hosts=4"
        for h in cl.hosts:
            for arr in h.server.device_tables():
                check(arr.devices() == {h.device},
                      f"{name}: host {h.hid} tables not on {h.device}")
        check(got.exact, f"{name}: an answer is not exact")
        check(np.array_equal(got.rows, ref.rows),
              f"{name}: routed rows != single-host rows")
        check(np.array_equal(got.rows, want),
              f"{name}: routed rows != core.containment")
        stats = cl.stats()
        print(f"[{name}] wall {dt:.3f}s; {len(queries)} routed rows == "
              f"single-host rows == core.containment, all exact; tables "
              f"of host i on {[str(h.device) for h in cl.hosts]}; shards "
              f"{[len(h.rows) for h in cl.hosts]}; escalated_cells "
              f"{stats.get('shards_escalated_cells', 0)}, "
              f"host_fallback_cells "
              f"{stats.get('shards_host_fallback_cells', 0)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-host cluster path")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; compile cache {cache_dir}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; there is no CPU fallback",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chip else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.four_chip:
            four_chip(clock, devices[:4])
        else:
            one_chip(clock)
    except Failure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"[total] wall {time.perf_counter() - t0:.3f}s, compile "
          f"{clock.seconds:.3f}s ({clock.compiles} compiles, "
          f"{clock.cache_hits} cache hits)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
