"""Multi-host serving cluster: every topology must be bit-equal to its
single-host counterpart on the same inputs.

The cluster is pure protocol over the already-exact shard servers -
placement (intact depth-1 subtrees / flat ranges), cross-host request
batching, two-level caching, the sharded-window all-reduce, and
writer->replica delta shipping - so the tests here are differential:
routed results vs ``PatternServer``, sharded-window frequent maps vs
``StreamingBank`` and the batch re-mine oracle, replica serving vs the
writer.  Hosts are in-process simulations; the subprocess smoke pins
one host per virtual CPU device following test_distributed.py's
conventions."""
import os
import random
import subprocess
import sys

import numpy as np
import pytest
from conftest import random_db

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - CI shim (see hypothesis_compat)
    from hypothesis_compat import given, settings, strategies as st

from repro.core.reverse_search import mine_gtrace_rs
from repro.mining.driver import AcceleratedMiner
from repro.serving.bank import compile_bank, sequence_fingerprint
from repro.serving.cluster import (
    ReplicaGroup,
    ServingCluster,
    ShardedStreamingBank,
)
from repro.serving.router import plan_placement
from repro.serving.server import PatternServer
from repro.serving.streaming import StreamingBank
from repro.serving.trie import build_trie

MINSUP, MAX_LEN, W = 3, 3, 8


def _bank(seed, n_seq=10, sigma=2, max_len=MAX_LEN):
    db = random_db(seed, n_seq=n_seq)
    return compile_bank(
        AcceleratedMiner(db).mine_rs(sigma, max_len=max_len))


def _spread(queries, n_hosts):
    reqs = {h: [] for h in range(n_hosts)}
    for i, s in enumerate(queries):
        reqs[i % n_hosts].append(s)
    return reqs


def _oracle(seqs):
    return dict(mine_gtrace_rs(seqs, MINSUP, max_len=MAX_LEN).patterns)


# ------------------------------------------------------- routed serving
@pytest.mark.slow
@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_routed_cluster_equals_single_host(seed):
    """The tentpole serving contract: containment bits and top-k of
    queries routed through any host split are bit-equal to the
    single-host PatternServer, in both bank layouts."""
    rng = random.Random(seed)
    layout = rng.choice(["flat", "trie"])
    H = rng.choice([2, 3])
    bank = _bank(seed % 50)
    if not bank.n_patterns:
        return
    queries = random_db(seed % 50 + 1, n_seq=7)
    srv = PatternServer(bank, bank_layout=layout)
    want = [srv.query_one(s) for s in queries]
    cl = ServingCluster(bank, H, bank_layout=layout)
    got = cl.query_multi(_spread(queries, H))
    for i, w in enumerate(want):
        r = got[i % H][i // H]
        np.testing.assert_array_equal(r.contained, w.contained)
        assert r.topk == w.topk
        assert r.fingerprint == w.fingerprint


@pytest.mark.parametrize("layout", ["flat", "trie"])
def test_single_host_cluster_is_degenerate(layout):
    """H=1 must reproduce the PatternServer bitwise - the cluster adds
    routing, not semantics."""
    bank = _bank(23)
    queries = random_db(24, n_seq=6)
    srv = PatternServer(bank, bank_layout=layout)
    want = srv.query(queries)
    cl = ServingCluster(bank, 1, bank_layout=layout)
    got = cl.query(queries, host=0)
    for r, w in zip(got, want):
        np.testing.assert_array_equal(r.contained, w.contained)
        assert r.topk == w.topk
    assert len(cl.hosts) == 1
    assert len(cl.hosts[0].rows) == bank.n_patterns


@pytest.mark.parametrize("layout", ["flat", "trie"])
def test_empty_shard_cluster(layout):
    """More hosts than depth-1 subtrees (trie) or patterns (flat)
    leaves empty shards; they answer nothing and break nothing."""
    bank = _bank(23)
    trie = build_trie(bank)
    n_subtrees = len(trie.levels[0]) if trie.depth else 0
    H = (n_subtrees if layout == "trie" else bank.n_patterns) + 2
    cl = ServingCluster(bank, H, bank_layout=layout)
    assert any(len(h.rows) == 0 for h in cl.hosts), "need an empty shard"
    queries = random_db(24, n_seq=5)
    srv = PatternServer(bank, bank_layout=layout)
    np.testing.assert_array_equal(
        cl.exact_rows(queries), srv.exact_rows(queries))


def test_placement_partitions_bank():
    bank = _bank(29)
    trie = build_trie(bank)
    for layout, t in (("flat", None), ("trie", trie)):
        for H in (1, 2, 5):
            pl = plan_placement(bank, H, layout=layout, trie=t)
            got = sorted(
                int(i) for rows in pl.rows for i in rows)
            assert got == list(range(bank.n_patterns)), (layout, H)
    # trie placement keeps every depth-1 subtree on one host
    pl = plan_placement(bank, 3, layout="trie", trie=trie)
    anc = {}
    for row in range(bank.n_patterns):
        n = int(trie.terminal_node[row])
        while trie.node_parent[n] >= 0:
            n = int(trie.node_parent[n])
        anc[row] = n
    owner = {}
    for s, rows in enumerate(pl.rows):
        for r in rows:
            a = anc[int(r)]
            assert owner.setdefault(a, s) == s, \
                "depth-1 subtree split across shards"


def test_two_level_cache_cross_host_hits():
    """A sequence first served via host 0 is an L2 hit when it later
    arrives on host 1 (owner-keyed), and an L1 hit on replay at its own
    arrival host - all serving identical rows."""
    bank = _bank(31)
    queries = random_db(32, n_seq=6)
    cl = ServingCluster(bank, 2, bank_layout="flat")
    first = cl.query(queries, host=0)
    assert cl.router.stats["misses"] == len(
        {r.fingerprint for r in first})
    again = cl.query(queries, host=1)  # other host: L2 (owner) hits
    assert cl.router.stats["l2_hits"] > 0
    replay = cl.query(queries, host=1)  # now in host 1's own L1
    assert cl.router.stats["l1_hits"] > 0
    assert cl.router.stats["misses"] == len(
        {r.fingerprint for r in first}), "caches must absorb replays"
    for a, b, c in zip(first, again, replay):
        np.testing.assert_array_equal(a.contained, b.contained)
        np.testing.assert_array_equal(a.contained, c.contained)
        assert b.cached and c.cached


def test_cluster_row_mask_matches_single_host():
    bank = _bank(33)
    queries = random_db(34, n_seq=5)
    mask = np.arange(bank.n_patterns) % 3 != 0
    for layout in ("flat", "trie"):
        srv = PatternServer(bank, bank_layout=layout)
        srv.set_row_mask(mask)
        cl = ServingCluster(bank, 2, bank_layout=layout)
        cl.set_row_mask(mask)
        np.testing.assert_array_equal(
            cl.exact_rows(queries), srv.exact_rows(queries))
        cl.set_row_mask(None)
        srv.set_row_mask(None)
        np.testing.assert_array_equal(
            cl.exact_rows(queries), srv.exact_rows(queries))


def test_l2_entries_survive_tombstone():
    """A pure tombstone (rows only leaving the active set) patches the
    cached rows per-column instead of dropping them: untouched-row L2/L1
    entries survive, replays stay cache hits, and the patched bits are
    bit-equal to a fresh masked join.  A recovery (masked -> active)
    still clears everything - cached False bits are unrecoverable."""
    bank = _bank(35)
    queries = random_db(36, n_seq=5)
    cl = ServingCluster(bank, 2, bank_layout="flat")
    cl.query(queries, host=0)
    cl.query(queries, host=1)  # populate L1s on both hosts via L2
    n_l2 = sum(len(h.l2) for h in cl.hosts)
    n_l1 = sum(len(h.l1) for h in cl.hosts)
    assert n_l2 > 0 and n_l1 > 0
    mask = np.ones(bank.n_patterns, bool)
    mask[:: 2] = False  # tombstone half the bank
    cl.set_row_mask(mask)
    assert sum(len(h.l2) for h in cl.hosts) == n_l2, \
        "tombstone must not evict untouched L2 entries"
    assert sum(len(h.l1) for h in cl.hosts) == n_l1
    assert cl.router.stats["mask_patches"] == 1
    misses = cl.router.stats["misses"]
    got = cl.query(queries, host=0)
    assert cl.router.stats["misses"] == misses, \
        "patched entries must keep serving as cache hits"
    assert all(r.cached for r in got)
    srv = PatternServer(bank, bank_layout="flat")
    srv.set_row_mask(mask)
    np.testing.assert_array_equal(
        np.stack([r.contained for r in got]), srv.exact_rows(queries))
    # deepening the tombstone patches again; recovering a row clears
    mask2 = mask.copy()
    mask2[1] = False
    cl.set_row_mask(mask2)
    assert sum(len(h.l2) for h in cl.hosts) == n_l2
    assert cl.router.stats["mask_patches"] == 2
    cl.set_row_mask(mask)  # row 1 comes back: cached False is stale
    assert cl.router.stats["mask_clears"] == 1
    assert sum(len(h.l2) for h in cl.hosts) == 0
    got = cl.query(queries, host=0)
    np.testing.assert_array_equal(
        np.stack([r.contained for r in got]), srv.exact_rows(queries))


# ------------------------------------------------ async admission pipeline
def _flatten(results, queries, n_hosts):
    """Per-query results in original order from a _spread drain."""
    return [results[i % n_hosts][i // n_hosts]
            for i in range(len(queries))]


@pytest.mark.parametrize("layout", ["flat", "trie"])
def test_async_submit_collect_equals_route_and_single_host(layout):
    """The tentpole contract: the continuous-batching pipeline
    (submit -> flush -> collect) is bit-equal to the synchronous
    ``route`` AND to the single-host PatternServer, and every exact-tier
    answer is flagged exact."""
    bank = _bank(41)
    queries = random_db(42, n_seq=8)
    srv = PatternServer(bank, bank_layout=layout)
    want = srv.query(queries)
    sync = ServingCluster(bank, 2, bank_layout=layout)
    ref = _flatten(sync.query_multi(_spread(queries, 2)), queries, 2)
    cl = ServingCluster(bank, 2, bank_layout=layout, flush_batch=3)
    got = _flatten(cl.collect(cl.submit(_spread(queries, 2))),
                   queries, 2)
    for w, a, b in zip(want, ref, got):
        np.testing.assert_array_equal(a.contained, w.contained)
        np.testing.assert_array_equal(b.contained, w.contained)
        assert a.topk == b.topk == w.topk
        assert a.exact and b.exact
    assert cl.router.depth() == 0, "collect must drain the pipeline"


def test_inflight_dedup_shares_join():
    """A fingerprint resubmitted while its first copy is queued or on
    device piggybacks on the same join: one device batch, one shared
    row, counted as an in-flight hit instead of a second miss."""
    bank = _bank(31)
    queries = random_db(32, n_seq=4)
    ufps = len({sequence_fingerprint(s) for s in queries})
    cl = ServingCluster(bank, 2, bank_layout="flat", flush_batch=ufps)
    t1 = cl.submit(_spread(queries, 2))       # batch trigger: in flight
    assert cl.router.stats["flush_batch"] == 1
    batches = cl.router.stats["shard_batches"]
    t2 = cl.submit(_spread(queries, 2))       # same fps, still unfenced
    assert cl.router.stats["inflight_hits"] == ufps
    assert cl.router.stats["misses"] == ufps, \
        "piggybacked repeats must not count as misses"
    assert cl.router.stats["shard_batches"] == batches, \
        "piggybacked repeats must not launch a second join"
    r1 = _flatten(cl.collect(t1), queries, 2)
    r2 = _flatten(cl.collect(t2), queries, 2)
    for a, b in zip(r1, r2):
        assert a.contained is b.contained, "tickets share the row"
        assert a.topk == b.topk


@pytest.mark.parametrize("layout", ["flat", "trie"])
def test_shed_tier_is_flagged_approximate_superset(layout):
    """Load shedding: past ``shed_depth`` new misses are answered from
    the host-side counts prescreen - a sound overapproximation of the
    exact bits, flagged ``exact=False``, never cached; the default
    (no ``shed_depth``) never sheds."""
    bank = _bank(33)
    queries = random_db(34, n_seq=5)
    srv = PatternServer(bank, bank_layout=layout)
    exact = srv.exact_rows(queries)
    ufps = len({sequence_fingerprint(s) for s in queries})
    cl = ServingCluster(bank, 2, bank_layout=layout, shed_depth=0)
    got = _flatten(cl.collect(cl.submit(_spread(queries, 2))),
                   queries, 2)
    assert cl.router.stats["shed_prescreen"] == ufps
    assert cl.router.stats["misses"] == ufps, \
        "shed requests still count as misses"
    for i, r in enumerate(got):
        assert not r.exact
        assert not (exact[i] & ~r.contained).any(), \
            "prescreen must never drop a true containment"
    assert all(not h.l1 and not h.l2 for h in cl.hosts), \
        "approximate rows must never enter the caches"
    # default config: exactness is the contract, nothing sheds
    cl2 = ServingCluster(bank, 2, bank_layout=layout, flush_batch=2)
    got2 = _flatten(cl2.collect(cl2.submit(_spread(queries, 2))),
                    queries, 2)
    assert cl2.router.stats["shed_prescreen"] == 0
    for i, r in enumerate(got2):
        assert r.exact
        np.testing.assert_array_equal(r.contained, exact[i])


def test_deadline_flush_under_fake_clock():
    """Deadline-aware flushing is deterministic under an injected
    clock: nothing flushes before ``max_wait``, the head-of-queue age
    triggers exactly one deadline flush at the boundary, and the
    queue-depth gauge tracks ``depth()`` throughout."""
    bank = _bank(35)
    queries = random_db(36, n_seq=6)
    now = [0.0]
    cl = ServingCluster(bank, 2, bank_layout="flat", max_wait=1.0,
                        clock=lambda: now[0])
    gauge = lambda: cl.metrics.snapshot(
        "cluster.router")["cluster.router.queue_depth"]
    t1 = cl.submit(_spread(queries[:3], 2))
    ufps = len({sequence_fingerprint(s) for s in queries[:3]})
    assert cl.router.depth() == ufps == gauge()
    now[0] = 0.99
    cl.poll()
    assert cl.router.stats["flush_deadline"] == 0, "before the deadline"
    assert cl.router.depth() == ufps, "queue intact"
    now[0] = 1.0
    cl.poll()
    assert cl.router.stats["flush_deadline"] == 1, "head aged past max_wait"
    assert cl.router.depth() == ufps == gauge(), \
        "launched but unfenced joins still count toward depth"
    t2 = cl.submit(_spread(queries[3:], 2))   # fresh queue, young head
    results = cl.collect()                    # all tickets, submit order
    assert cl.router.stats["flush_force"] >= 1
    assert cl.router.depth() == 0 == gauge()
    srv = PatternServer(bank, bank_layout="flat")
    want = srv.exact_rows(queries)
    got = (_flatten(results[0], queries[:3], 2)
           + _flatten(results[1], queries[3:], 2))
    for i, r in enumerate(got):
        np.testing.assert_array_equal(r.contained, want[i])
        assert r.exact


def test_async_cache_parity_with_sync_route():
    """Satellite: cache behavior is path-independent.  Driving the same
    interleaved drains through ``route`` and through submit+collect
    yields identical hit/miss counters, identical L1/L2 key sets in
    identical LRU order, and identical post-mask-patch cache contents."""
    bank = _bank(37)
    pool = random_db(38, n_seq=10)
    rng = random.Random(7)
    drains = [
        _spread([pool[rng.randrange(len(pool))]
                 for _ in range(rng.randint(1, 4))], 2)
        for _ in range(6)
    ]
    sync = ServingCluster(bank, 2, bank_layout="flat")
    async_ = ServingCluster(bank, 2, bank_layout="flat", flush_batch=2)
    for d in drains:
        ra = sync.query_multi(d)
        rb = async_.collect(async_.submit(d))
        for hid in ra:
            for a, b in zip(ra[hid], rb[hid]):
                np.testing.assert_array_equal(a.contained, b.contained)
                assert a.cached == b.cached and a.topk == b.topk
    for key in ("queries", "l1_hits", "l2_hits", "misses"):
        assert sync.router.stats[key] == async_.router.stats[key], key
    for ha, hb in zip(sync.hosts, async_.hosts):
        assert list(ha.l1.keys()) == list(hb.l1.keys()), "L1 LRU order"
        assert list(ha.l2.keys()) == list(hb.l2.keys()), "L2 LRU order"
    # the copy-on-write tombstone patch sees the same cache state
    mask = np.arange(bank.n_patterns) % 2 == 0
    sync.set_row_mask(mask)
    async_.set_row_mask(mask)
    assert (sync.router.stats["mask_patches"]
            == async_.router.stats["mask_patches"] == 1)
    for ha, hb in zip(sync.hosts, async_.hosts):
        for ca, cb in ((ha.l1, hb.l1), (ha.l2, hb.l2)):
            for fp in ca:
                np.testing.assert_array_equal(ca[fp], cb[fp])


def test_exact_rows_counts_queries():
    """Satellite bugfix: the routed path enters the shard servers via
    ``exact_rows``/``launch_rows``, which used to skip the ``queries``
    bump - per-host query counters read 0 in the cluster bench."""
    bank = _bank(23)
    queries = random_db(24, n_seq=5)
    srv = PatternServer(bank)
    srv.exact_rows(queries)
    assert srv.stats["queries"] == len(queries)
    cl = ServingCluster(bank, 2)
    cl.exact_rows(queries)
    for h in cl.hosts:
        if len(h.rows):
            assert h.server.stats["queries"] == len(queries)


def test_row_mask_requires_quiescent_pipeline():
    """In-flight joins were launched against the pre-mask requirements
    and ticket-held rows escape the copy-on-write patch, so re-masking
    with uncollected tickets must refuse - with a typed error that
    names the counts and survives ``python -O`` (serving.faults)."""
    from repro.serving.faults import PipelineBusyError

    bank = _bank(39)
    queries = random_db(40, n_seq=3)
    cl = ServingCluster(bank, 2, bank_layout="flat")
    ticket = cl.submit(_spread(queries, 2))
    mask = np.ones(bank.n_patterns, bool)
    mask[0] = False
    with pytest.raises(PipelineBusyError) as exc:
        cl.set_row_mask(mask)
    assert exc.value.tickets == 1
    assert exc.value.queued + exc.value.inflight > 0
    cl.collect(ticket)
    cl.set_row_mask(mask)  # quiescent: fine


# ------------------------------------------------------- sharded window
@pytest.mark.slow
@given(st.integers(0, 10_000))
@settings(max_examples=6, deadline=None)
def test_sharded_window_equals_single_host_streaming(seed):
    """The sharded-window protocol contract: after every refresh - and
    regardless of layout or host count - the frequent map is bit-equal
    to the single-host StreamingBank AND to a batch re-mine of the
    window."""
    rng = random.Random(seed)
    layout = rng.choice(["flat", "trie"])
    H = rng.choice([2, 4])
    db = random_db(seed % 40, n_seq=W)
    ref = StreamingBank.from_db(
        db, minsup=MINSUP, window=W, max_len=MAX_LEN, bank_layout=layout)
    sh = ShardedStreamingBank.from_db(
        db, minsup=MINSUP, n_hosts=H, window=W, max_len=MAX_LEN,
        bank_layout=layout)
    assert sh.window_seqs == ref.window_seqs
    for step in range(3):
        batch = random_db(1000 * seed + step, n_seq=rng.randint(1, 4))
        ref.observe(batch)
        sh.observe(batch)
        assert sh.window_seqs == ref.window_seqs
        if rng.random() < 0.5:
            full = rng.random() < 0.25
            a, b = ref.refresh(full=full), sh.refresh(full=full)
            assert a == b == _oracle(sh.window_seqs)
    a, b = ref.refresh(), sh.refresh()
    assert a == b == _oracle(sh.window_seqs)


def test_sharded_window_no_tombstones_continuously_exact():
    """With tombstones off nothing is ever masked, so the all-reduced
    partial supports equal the single-host maintained supports after
    every observe - not just at refresh points."""
    db = random_db(5, n_seq=W)
    ref = StreamingBank.from_db(
        db, minsup=MINSUP, window=W, max_len=MAX_LEN, tombstones=False)
    sh = ShardedStreamingBank.from_db(
        db, minsup=MINSUP, n_hosts=2, window=W, max_len=MAX_LEN,
        tombstones=False)
    for step in range(3):
        batch = random_db(7000 + step, n_seq=3)
        ref.observe(batch)
        sh.observe(batch)
        assert np.array_equal(sh._allreduce_support(), ref.support)
        assert sh.window_seqs == ref.window_seqs
    assert ref.refresh() == sh.refresh()


def test_sharded_window_empty_bank_grows():
    """An empty seed bank must grow through the full-recompile path
    once churn makes patterns frequent (mirrors the single-host
    test)."""
    sh = ShardedStreamingBank.from_db(
        random_db(1, n_seq=2), minsup=MINSUP, n_hosts=2, window=W,
        max_len=MAX_LEN)
    assert sh.bank.n_patterns == 0
    sh.observe(random_db(7, n_seq=6))
    got = sh.refresh()
    assert got == _oracle(sh.window_seqs) and got
    assert sh.stats["full_refreshes"] == 1


def test_sharded_window_queries_match_single_host_bits():
    """Routed streaming queries serve the same containment bits as the
    single-host streaming bank's server (tombstone cuts included once
    both sides refreshed)."""
    db = random_db(17, n_seq=W)
    ref = StreamingBank.from_db(
        db, minsup=MINSUP, window=W, max_len=MAX_LEN)
    sh = ShardedStreamingBank.from_db(
        db, minsup=MINSUP, n_hosts=2, window=W, max_len=MAX_LEN)
    batch = random_db(300, n_seq=3)
    ref.observe(batch)
    sh.observe(batch)
    ref.refresh()
    sh.refresh()
    queries = db[:3]
    a = ref.query(queries, k=5)
    b = sh.query(queries, host=1, k=5)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.contained, y.contained)
        assert x.topk == y.topk


# ------------------------------------------------------------- replicas
def test_replica_serves_during_writer_refresh_then_converges():
    """A replica keeps serving its pre-refresh masked bank while the
    writer refreshes (deltas queued, reads never block), and becomes
    bit-equal to the writer once the deltas ship."""
    db = random_db(21, n_seq=W)
    writer = StreamingBank.from_db(
        db, minsup=MINSUP, window=W, max_len=MAX_LEN, bank_layout="trie")
    group = ReplicaGroup(writer, 2)
    queries = random_db(22, n_seq=5)
    before = group.query(queries, replica=0, k=5)
    # the writer slides + refreshes; replica 0 has not synced yet
    writer.observe(random_db(400, n_seq=4))
    writer.refresh()
    assert group.lag(0) > 0
    during = group.query(queries, replica=0, k=5)
    for a, b in zip(before, during):
        np.testing.assert_array_equal(a.contained, b.contained)
        assert a.topk == b.topk
    group.sync(0)
    assert group.lag(0) == 0
    after = group.query(queries, replica=0, k=5)
    want = writer.query(queries, k=5)
    for a, w in zip(after, want):
        np.testing.assert_array_equal(a.contained, w.contained)
        assert a.topk == w.topk
    # replica 1 syncs independently and converges too
    group.sync(1)
    for a, w in zip(group.query(queries, replica=1, k=5), want):
        np.testing.assert_array_equal(a.contained, w.contained)


def test_replica_applies_extend_delta_without_recompile():
    """When the writer's incremental refresh appends patterns, replicas
    grow via extend_bank/extend_trie (the shipped delta), not a
    recompile - and serve the extended bank exactly."""
    found = None
    for seed in range(40):
        db = random_db(seed, n_seq=W)
        w = StreamingBank.from_db(
            db, minsup=MINSUP, window=W, max_len=MAX_LEN,
            bank_layout="trie")
        if not w.bank.n_patterns:
            continue
        g = ReplicaGroup(w, 1)
        w.observe(random_db(5000 + seed, n_seq=4))
        w.refresh()
        if w.stats["added"] > 0 and w.stats["full_refreshes"] == 0:
            found = (w, g)
            break
    assert found, "no seed produced an in-place bank extension"
    w, g = found
    g.sync()
    rep = g.replicas[0]
    assert rep.bank.n_patterns == w.bank.n_patterns
    assert rep.bank.patterns == w.bank.patterns
    queries = w.window_seqs[:4]
    for a, b in zip(w.query(queries, k=5), g.query(queries, k=5)):
        np.testing.assert_array_equal(a.contained, b.contained)
        assert a.topk == b.topk


# ---------------------------------------------------- multi-device smoke
SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src"); sys.path.insert(0, "tests")
import numpy as np
import jax
from conftest import random_db
from repro.mining.driver import AcceleratedMiner
from repro.serving.bank import compile_bank
from repro.serving.cluster import ServingCluster
from repro.serving.server import PatternServer

db = random_db(3, n_seq=10)
bank = compile_bank(AcceleratedMiner(db).mine_rs(2, max_len=3))
assert bank.n_patterns > 0
queries = random_db(9, n_seq=8)
devs = jax.devices()
assert len(devs) == 8, devs
for layout in ("flat", "trie"):
    ref = PatternServer(bank, bank_layout=layout)
    want = ref.exact_rows(queries)
    cl = ServingCluster(bank, 8, bank_layout=layout, devices=devs)
    assert len({h.device for h in cl.hosts}) == 8, "one device per host"
    got = cl.exact_rows(queries)
    assert np.array_equal(got, want), layout
    # each host's tables live on its own device, also after a re-upload
    for mask in (None, np.arange(bank.n_patterns) % 2 == 0):
        cl.set_row_mask(mask)
        for h in cl.hosts:
            for arr in h.server.device_tables():
                assert arr.devices() == {h.device}, (layout, h.hid)
print("CLUSTER-OK", bank.n_patterns)
"""


@pytest.mark.slow
@pytest.mark.subprocess
def test_cluster_8dev_smoke():
    """One simulated host per virtual CPU device (the jax.distributed
    stand-in): routed rows must equal the single-host server."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        cwd=os.path.join(os.path.dirname(__file__), ".."), env=env,
    )
    assert "CLUSTER-OK" in r.stdout, r.stdout + "\n" + r.stderr
