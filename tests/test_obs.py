"""Observability layer: the metrics registry, the span tracer, and -
the load-bearing contract - the disabled-tracing no-op path.

Tracing is off by default and must be *free*: with the tracer
disabled, every instrumented subsystem (mining wavefront, serving
joins, streaming refreshes, cluster routing) must produce bit-identical
results, identical device dispatch counts, and zero recorded events
compared to the uninstrumented seed code; enabling the buffer or
running a JAX profiler session (the tracer's second sink) must never
change a result either, and no mode blocks on the device.  The
registry's reset semantics are the other
contract: counters live in the registry, so component rebuilds
(``refresh(full=True)`` recompiling a server, the sharded-window
protocol re-planning its router) accumulate instead of silently
zeroing."""
import contextlib
import glob
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import numpy as np
import pytest
from conftest import random_db

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - CI shim (see hypothesis_compat)
    from hypothesis_compat import given, settings, strategies as st

from repro.mining.driver import AcceleratedMiner
from repro.obs import MetricsRegistry, trace
from repro.serving.bank import compile_bank
from repro.serving.cluster import ServingCluster, ShardedStreamingBank
from repro.serving.server import PatternServer
from repro.serving.streaming import StreamingBank

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
import trace_report  # noqa: E402

MINSUP, MAX_LEN = 2, 3


@pytest.fixture(autouse=True)
def _tracer_off():
    """Every test starts and ends with the global tracer disabled and
    empty - a leaked enabled tracer would perturb every later test."""
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


@contextlib.contextmanager
def _profiled(log_dir=None):
    """A JAX profiler session with the Python tracer off; its trace is
    kept under ``log_dir`` (dropped when none is given)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    path = log_dir or tempfile.mkdtemp(prefix="obs-profile-")
    jax.profiler.start_trace(path, profiler_options=opts)
    try:
        yield path
    finally:
        jax.profiler.stop_trace()
        if log_dir is None:
            shutil.rmtree(path, ignore_errors=True)


@contextlib.contextmanager
def _traced(mode):
    """Tracing on: the in-memory buffer (``"enable"``) or a profiler
    session (``"profiler"``)."""
    if mode == "enable":
        trace.enable()
        try:
            yield
        finally:
            trace.disable()
    else:
        with _profiled():
            yield


def _host_events(log_dir):
    """``{name: count}`` of the events on the profiler's host planes."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out[e.name] = out.get(e.name, 0) + 1
    return out


def _spread(queries, n_hosts):
    reqs = {h: [] for h in range(n_hosts)}
    for i, s in enumerate(queries):
        reqs[i % n_hosts].append(s)
    return reqs


# ========================================================== registry
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("m.calls")
    c.inc()
    c.inc(2)
    assert c.value == 3
    g = reg.gauge("m.depth")
    g.set(7)
    g.set(4)
    assert g.value == 4
    h = reg.histogram("m.wave")
    for v in (1, 5, 3):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["m.calls"] == 3
    assert snap["m.depth"] == 4
    assert snap["m.wave.count"] == 3
    assert snap["m.wave.sum"] == 9
    assert snap["m.wave.min"] == 1
    assert snap["m.wave.max"] == 5
    assert snap["m.wave.mean"] == 3


def test_registry_collision_returns_same_object():
    """The rebuild-survival mechanism: re-registering a name returns
    the SAME metric, so a recompiled component keeps accumulating."""
    reg = MetricsRegistry()
    a = reg.counter("srv.queries")
    a.inc(5)
    b = reg.counter("srv.queries")
    assert a is b and b.value == 5
    with pytest.raises(TypeError):
        reg.gauge("srv.queries")  # a name owns exactly one type


def test_snapshot_delta_reset():
    reg = MetricsRegistry()
    reg.counter("a.x").inc(10)
    reg.counter("b.y").inc(1)
    before = reg.snapshot()
    reg.counter("a.x").inc(4)
    assert reg.delta(before) == {"a.x": 4, "b.y": 0}
    assert reg.snapshot("a") == {"a.x": 14}
    reg.reset("a")
    assert reg.counter("a.x").value == 0
    assert reg.counter("b.y").value == 1  # prefix reset is scoped
    reg.reset()
    assert reg.counter("b.y").value == 0


def test_stats_view_is_a_mutable_mapping():
    """The facade the migrated call sites rely on: iteration shows
    declared keys, += and = write through to registry counters, and
    the counter monotonicity contract holds - increments pass through,
    the legacy reset-by-assignment idiom still works but WARNS (route
    resets through ``MetricsRegistry.reset``), and any other decrease
    raises."""
    reg = MetricsRegistry()
    view = reg.view("srv", keys=["queries", "hits"])
    assert dict(view) == {"queries": 0, "hits": 0}
    view["queries"] += 3
    assert reg.counter("srv.queries").value == 3
    view["new_key"] = 2  # unknown keys register on assignment
    assert "new_key" in view and reg.counter("srv.new_key").value == 2
    with pytest.warns(UserWarning, match="reset-by-assignment"):
        view["queries"] = 0  # the old bench reset idiom: works, warns
    assert view["queries"] == 0
    with pytest.raises(ValueError, match="monotonicity"):
        view["new_key"] = 1  # 2 -> 1 is neither inc nor reset
    assert view["new_key"] == 2
    reg.reset("srv")  # the sanctioned path: silent
    assert all(v == 0 for v in dict(view).values())
    with pytest.raises(KeyError):
        view["never_declared"]
    with pytest.raises(TypeError):
        del view["queries"]


def test_counter_set_contract():
    """``Counter.set`` is not assignment: non-zero raises (counters
    are monotone), zero warns (deprecated reset path)."""
    reg = MetricsRegistry()
    c = reg.counter("m.x")
    c.inc(5)
    with pytest.raises(ValueError, match="monotonicity"):
        c.set(3)
    assert c.value == 5
    with pytest.warns(UserWarning, match="reset-by-assignment"):
        c.set(0)
    assert c.value == 0


# ================================================== bucket histogram
def test_bucket_histogram_quantile_bounds():
    """quantile(q) returns the upper edge of the bucket holding the
    q-th observation: an exact bound - never below the true quantile,
    within one log-bucket width above it."""
    from repro.obs import BucketHistogram
    reg = MetricsRegistry()
    h = reg.bucket_histogram("m.lat")
    assert h.quantile(0.5) == 0.0  # empty histogram
    rng = np.random.default_rng(7)
    vals = sorted(10.0 ** rng.uniform(-5, 1, size=500))
    for v in vals:
        h.observe(v)
    assert h.count == 500 and h.sum == pytest.approx(sum(vals))
    for q in (0.01, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
        true = vals[min(499, max(0, int(np.ceil(q * 500)) - 1))]
        bound = h.quantile(q)
        assert bound >= true * (1 - 1e-12)
        # 8 buckets/decade: the bound is < one bucket width above
        assert bound <= true * 10.0 ** (1 / 8) * (1 + 1e-9)
    s = h.summary()
    assert s["p50"] == h.quantile(0.5)
    assert s["p99"] == h.quantile(0.99)
    snap = reg.snapshot()
    assert snap["m.lat.count"] == 500 and "m.lat.p95" in snap
    # overflow bucket reports the tracked exact max
    h.observe(1e6)
    assert h.quantile(1.0) == 1e6
    h.reset()
    assert h.count == 0 and sum(h.counts) == 0
    assert isinstance(h, type(reg.histogram("m.lat")))  # same object
    assert type(h) is BucketHistogram


def test_bucket_histogram_single_value():
    from repro.obs import BucketHistogram
    h = BucketHistogram("x")
    h.observe(0.003)
    for q in (0.0, 0.5, 1.0):
        assert h.quantile(q) >= 0.003
        assert h.quantile(q) <= 0.003 * 10.0 ** (1 / 8)


# ============================================================ tracer
def test_disabled_tracer_is_shared_noop():
    assert not trace.enabled()
    assert trace.span("x") is trace.span("y") is trace.root_or_span("z")
    # categories and arguments change nothing on the fast path
    assert trace.span("x", cat="dispatch", n=1) is trace.span("y")
    with trace.span("x", cat="dispatch", n=1):
        pass
    assert trace.tracer.events == []


def test_profiler_session_turns_the_annotation_sink_on(tmp_path):
    """A running profiler session is the only switch of the second
    sink: spans become ``TraceAnnotation``s while it runs (the buffer
    stays empty) and the shared no-op again once it stops."""
    noop = trace.span("x")
    with _profiled(str(tmp_path)) as path:
        s = trace.span("t.leaf", cat="dispatch", n=3)
        assert isinstance(s, jax.profiler.TraceAnnotation)
        root = trace.root_or_span("t.root", n=1)
        assert isinstance(root, jax.profiler.TraceAnnotation)
        with root:
            with s:
                pass
        # the buffer and the profiler together: one span, both sinks
        trace.enable()
        with trace.root_or_span("t.both"):
            pass
        trace.disable()
    names = _host_events(path)
    assert trace.span("y") is noop
    assert names.get("t.root") == names.get("t.leaf") == 1
    assert names.get("t.both") == 1
    assert [e["name"] for e in trace.tracer.events] == ["t.both"]


def test_sampled_roots_reach_the_profiler(tmp_path):
    """Sampled and tail roots, and the spans under them, open their
    annotations too."""
    with _profiled(str(tmp_path)) as path:
        trace.enable_sampling(1.0)
        with trace.root_or_span("t.kept"):
            with trace.span("t.kept_child"):
                pass
        trace.enable_sampling(0.0)
        with trace.root_or_span("t.tail"):
            with trace.span("t.tail_child"):
                pass
        trace.disable()
    names = _host_events(path)
    for n in ("t.kept", "t.kept_child", "t.tail", "t.tail_child"):
        assert names.get(n) == 1, n


def test_span_nesting_and_trace_ids():
    trace.enable()
    with trace.root_or_span("outer", n=1):
        tid = trace.current_trace()
        assert tid is not None
        with trace.root_or_span("inner"):  # nested: same trace, host cat
            assert trace.current_trace() == tid
        with trace.span("leaf", cat="device"):
            pass
    assert trace.current_trace() is None
    with trace.root_or_span("outer2"):
        assert trace.current_trace() == tid + 1  # fresh id per root
    evs = {e["name"]: e for e in trace.tracer.events}
    assert evs["outer"]["cat"] == "wall"
    assert evs["inner"]["cat"] == "host"
    assert evs["leaf"]["cat"] == "device"
    assert evs["outer"]["args"] == {"n": 1}
    assert evs["leaf"]["trace"] == tid
    # children recorded before parents (exit order), all inside outer
    assert evs["leaf"]["ts"] >= evs["outer"]["ts"]
    assert (evs["leaf"]["ts"] + evs["leaf"]["dur"]
            <= evs["outer"]["ts"] + evs["outer"]["dur"] + 1.0)


def test_save_and_report_roundtrip(tmp_path):
    """Both export formats load, validate, and attribute >= 90% of
    wall time (every root's body is tiled by child spans here, as the
    instrumentation style mandates)."""
    trace.enable()
    for _ in range(3):
        # the children need real duration: coverage is self-time based,
        # so empty leaves would leave the root's own body dominant
        with trace.root_or_span("q.query"):
            with trace.span("q.cache", cat="cache"):
                time.sleep(0.002)
            with trace.span("q.join", cat="dispatch"):
                with trace.span("q.device", cat="device"):
                    time.sleep(0.002)
            with trace.span("q.finalize"):
                time.sleep(0.002)
    for suffix in ("t.json", "t.jsonl"):
        path = str(tmp_path / suffix)
        trace.save(path)
        events = trace_report.load_events(path)
        assert len(events) == len(trace.tracer.events)
        assert trace_report.validate(events) == []
        att = trace_report.attribute(events)
        assert att["n_traces"] == 3
        assert att["coverage"] >= 0.9
        total = (sum(att["buckets_us"].values())
                 + att["uninstrumented_us"])
        assert total == pytest.approx(att["wall_us"], rel=1e-6)
    # chrome export is valid trace-viewer input
    with open(str(tmp_path / "t.json")) as f:
        doc = json.load(f)
    assert all(e["ph"] == "X" for e in doc["traceEvents"])


def test_trace_report_rejects_malformed(tmp_path):
    bad = [{"name": "x", "cat": "nope", "ts": 0.0, "dur": 1.0,
            "trace": None}]
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        for e in bad:
            f.write(json.dumps(e) + "\n")
    problems = trace_report.validate(trace_report.load_events(path))
    assert problems  # unknown category + no wall root


# ========================================== no-op path: bit-identical
@pytest.mark.slow
@pytest.mark.parametrize("mode", ["enable", "profiler"])
@given(seed=st.integers(0, 10_000))
@settings(max_examples=6, deadline=None)
def test_mining_unchanged_by_tracing(mode, seed):
    """Disabled tracing adds zero device dispatches and changes no
    frequent map; enabling the buffer or running a profiler session
    changes no results or dispatch counts either."""
    # hypothesis reuses one fixture across examples: reset per example
    trace.disable()
    trace.clear()
    db = random_db(seed % 50, n_seq=8)
    base = AcceleratedMiner(db)
    want = base.mine_rs(MINSUP, max_len=MAX_LEN)
    assert trace.tracer.events == []  # disabled run recorded nothing

    m_off = AcceleratedMiner(db)
    got_off = m_off.mine_rs(MINSUP, max_len=MAX_LEN)
    assert got_off.patterns == want.patterns
    assert m_off.n_device_calls == base.n_device_calls

    with _traced(mode):
        m_on = AcceleratedMiner(db)
        got_on = m_on.mine_rs(MINSUP, max_len=MAX_LEN)
    assert got_on.patterns == want.patterns
    assert m_on.n_device_calls == base.n_device_calls
    if mode == "enable":
        assert any(e["cat"] == "wall" for e in trace.tracer.events)
    else:  # a profiler session leaves the buffer off
        assert trace.tracer.events == []


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["enable", "profiler"])
@given(seed=st.integers(0, 10_000))
@settings(max_examples=6, deadline=None)
def test_serving_unchanged_by_tracing(mode, seed):
    # hypothesis reuses one fixture across examples: reset per example
    trace.disable()
    trace.clear()
    db = random_db(seed % 50, n_seq=8)
    bank = compile_bank(
        AcceleratedMiner(db).mine_rs(MINSUP, max_len=MAX_LEN))
    if not bank.n_patterns:
        return
    queries = random_db(seed % 50 + 1, n_seq=6)
    layout = "trie" if seed % 2 else "flat"

    srv = PatternServer(bank, bank_layout=layout)
    want = srv.query(queries)
    assert trace.tracer.events == []

    with _traced(mode):
        srv_on = PatternServer(bank, bank_layout=layout)
        got = srv_on.query(queries)
    for r, w in zip(got, want):
        np.testing.assert_array_equal(r.contained, w.contained)
        assert r.topk == w.topk
    assert (srv_on.stats["device_batches"]
            == srv.stats["device_batches"])
    # the enabled run did record spans; a profiler session does not
    # switch the buffer on
    assert bool(trace.tracer.events) == (mode == "enable")


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["enable", "profiler"])
@given(seed=st.integers(0, 10_000))
@settings(max_examples=4, deadline=None)
def test_streaming_unchanged_by_tracing(mode, seed):
    # hypothesis reuses one fixture across examples: reset per example
    trace.disable()
    trace.clear()
    db = random_db(seed % 50, n_seq=8)
    batches = [random_db(seed % 50 + 1 + i, n_seq=2) for i in range(3)]

    def run():
        sb = StreamingBank.from_db(db, minsup=MINSUP, window=8,
                                   max_len=MAX_LEN, refresh_every=0)
        maps = []
        for b in batches:
            sb.observe(b)
            maps.append(sb.refresh())
        maps.append(sb.refresh(full=True))
        return maps

    want = run()
    assert trace.tracer.events == []
    with _traced(mode):
        got = run()
    assert got == want


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["enable", "profiler"])
@given(seed=st.integers(0, 10_000))
@settings(max_examples=4, deadline=None)
def test_cluster_unchanged_by_tracing(mode, seed):
    # hypothesis reuses one fixture across examples: reset per example
    trace.disable()
    trace.clear()
    db = random_db(seed % 50, n_seq=10)
    bank = compile_bank(
        AcceleratedMiner(db).mine_rs(MINSUP, max_len=MAX_LEN))
    if not bank.n_patterns:
        return
    queries = random_db(seed % 50 + 1, n_seq=6)
    H = 2 + seed % 2

    def run():
        cl = ServingCluster(bank, H)
        out = cl.query_multi(_spread(queries, H))
        # second drain replays the same queries through the caches
        out2 = cl.query_multi(_spread(queries, H))
        rows = [r.contained for h in sorted(out) for r in out[h]]
        rows += [r.contained for h in sorted(out2) for r in out2[h]]
        hits = cl.router.stats["l1_hits"] + cl.router.stats["l2_hits"]
        return np.stack(rows), hits, cl.router.stats["shard_batches"]

    want_rows, want_hits, want_batches = run()
    assert want_hits > 0  # the replay drain exercises the cache path
    assert trace.tracer.events == []
    with _traced(mode):
        got_rows, got_hits, got_batches = run()
    np.testing.assert_array_equal(got_rows, want_rows)
    assert (got_hits, got_batches) == (want_hits, want_batches)


# ===================================== counters survive full refresh
def test_streaming_stats_survive_full_refresh():
    """Satellite bugfix: the server's counters live in the bank's
    registry, so the full-refresh recompile (which rebuilds the
    PatternServer) accumulates instead of zeroing."""
    db = random_db(0, n_seq=8)
    sb = StreamingBank.from_db(db, minsup=MINSUP, window=8,
                               max_len=MAX_LEN, refresh_every=0)
    queries = random_db(1, n_seq=3)
    # exact_rows counts queries too, so streaming maintenance (window
    # containment during from_db/observe/refresh) contributes a base.
    base = sb.server.stats["queries"]
    assert base > 0
    sb.server.query(queries)
    before = sb.server.stats["queries"]
    assert before == base + len(queries)
    sb.observe(random_db(2, n_seq=2))
    sb.refresh(full=True)  # rebuilds self.server from scratch
    after = sb.server.stats["queries"]
    assert after >= before  # accumulated across the rebuild, never zeroed
    sb.server.query(queries)
    assert sb.server.stats["queries"] == after + len(queries)


def test_sharded_stats_survive_full_refresh():
    """Same contract one layer up: the router (re-planned on every
    full refresh) re-attaches to the sharded bank's registry."""
    db = random_db(0, n_seq=10)
    sb = ShardedStreamingBank.from_db(db, minsup=MINSUP, n_hosts=2,
                                      window=10, max_len=MAX_LEN)
    queries = random_db(1, n_seq=4)
    sb.cluster.query_multi(_spread(queries, 2))
    sb.cluster.query_multi(_spread(queries, 2))  # replay -> cache hits
    st = sb.cluster.router.stats
    hits_before = st["l1_hits"] + st["l2_hits"]
    queries_before = st["queries"]
    assert hits_before > 0
    sb.observe(random_db(2, n_seq=2))
    sb.refresh(full=True)  # re-plans placement, rebuilds the router
    st = sb.cluster.router.stats
    assert st["l1_hits"] + st["l2_hits"] == hits_before
    assert st["queries"] == queries_before
    snap = sb.metrics.snapshot("cluster.router")
    assert snap["cluster.router.queries"] == queries_before


# ============================================ end-to-end trace shape
def test_traced_cluster_query_coverage(tmp_path):
    """A real routed query's trace validates and attributes >= 90% of
    wall time - the per-artifact form of the tier-6 CI gate."""
    db = random_db(3, n_seq=10)
    bank = compile_bank(
        AcceleratedMiner(db).mine_rs(MINSUP, max_len=MAX_LEN))
    if not bank.n_patterns:
        pytest.skip("empty bank for this seed")
    queries = random_db(4, n_seq=6)
    cl = ServingCluster(bank, 2)
    cl.query_multi(_spread(queries, 2))  # warm jit outside the trace
    trace.clear()
    trace.enable()
    cl.query_multi(_spread(queries, 2))
    cl.query_multi(_spread(queries, 2))
    trace.disable()
    path = str(tmp_path / "route.jsonl")
    trace.save(path)
    events = trace_report.load_events(path)
    assert trace_report.validate(events) == []
    att = trace_report.attribute(events)
    # a routed drain on a toy bank is microseconds of wall, so the
    # fixed span-entry overhead shows up in the uninstrumented line;
    # the full >= 0.9 gate runs at bench scale (ci.sh tier-6, where
    # device batches dominate and coverage sits near 1.0)
    assert att["coverage"] >= 0.75
    assert att["n_traces"] >= 2  # one trace id per route drain
    names = {e["name"] for e in events}
    assert "cluster.route" in names and "cluster.cache" in names


# ======================================= the profiler sink, end to end
PHASE_SPANS = (
    "mining.pack", "mining.scan", "mining.aggregate", "mining.children",
    "mining.canonical", "mining.parent", "mining.rebuild",
    "serving.fingerprint", "serving.readback", "serving.escalate",
    "serving.token_index", "serving.join",
    "cluster.submit", "cluster.flush", "cluster.fence", "cluster.collect",
)


def _mine_and_serve():
    """One miner job, then its bank served through the admission
    pipeline (submit / collect, 4-query flushes): everything the
    profiler's trace has to name, with the observables tracing must
    not change."""
    db = random_db(3, n_seq=10)
    m = AcceleratedMiner(db)
    res = m.mine_rs(MINSUP, max_len=MAX_LEN)
    bank = compile_bank(res)
    cl = ServingCluster(bank, 1, flush_batch=4)
    tickets = [cl.submit({0: [q]}) for q in random_db(4, n_seq=8)]
    rows = np.stack([cl.collect(t)[0][0].contained for t in tickets])
    st = cl.stats()
    return (res.patterns, m.n_device_calls, rows,
            st["shards_device_batches"], st["flush_batch"])


def test_profiler_trace_holds_every_phase_span(tmp_path):
    """Mining and serving inside a profiler session: the host plane of
    the ``.xplane.pb`` names every host phase, and the patterns, rows
    and device calls are bit-identical to an untraced run."""
    want = _mine_and_serve()
    with _profiled(str(tmp_path)) as path:
        got = _mine_and_serve()
    assert got[0] == want[0]
    np.testing.assert_array_equal(got[2], want[2])
    assert got[1] == want[1] and got[3:] == want[3:]
    names = _host_events(path)
    missing = [n for n in PHASE_SPANS if n not in names]
    assert not missing, missing
    # one span per unit of work: a scan and an aggregate per device
    # call, a children span per expanded item
    assert names["mining.scan"] == names["mining.aggregate"] == want[1]
    assert names["mining.children"] >= names["mining.canonical"] > 0
    assert names["cluster.flush"] == want[4]
    assert trace.tracer.events == []


def test_full_tracing_never_blocks_on_the_device(monkeypatch):
    """The buffer's full mode records launch spans (``cat="dispatch"``)
    that end when the async call returns: no ``block_until_ready``
    from the tracer, and no host-clock ``device`` records."""
    blocks = []
    real_block = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocks.append(1) or real_block(x))
    db = random_db(3, n_seq=10)
    bank = compile_bank(
        AcceleratedMiner(db).mine_rs(MINSUP, max_len=MAX_LEN))
    queries = random_db(4, n_seq=6)
    want = [PatternServer(bank, bank_layout=lay).query(queries)
            for lay in ("flat", "trie")]
    trace.enable()
    got = [PatternServer(bank, bank_layout=lay).query(queries)
           for lay in ("flat", "trie")]
    ServingCluster(bank, 2).query_multi(_spread(queries, 2))
    trace.disable()
    assert blocks == []
    for g, w in zip(got, want):
        np.testing.assert_array_equal(
            np.stack([r.contained for r in g]),
            np.stack([r.contained for r in w]))
    evs = trace.tracer.events
    assert all(e["cat"] != "device" for e in evs)
    assert not any(e["name"].endswith(".device") for e in evs)
    dispatch = {e["name"] for e in evs if e["cat"] == "dispatch"}
    assert {"serving.token_index", "serving.prescreen", "serving.join",
            "serving.trie_advance"} <= dispatch


# ================================================== sampled tracing
def _cluster_run(bank, queries, H=2):
    """One fresh-cluster double drain; returns (rows, relevant stats)
    - the observables sampling must never change."""
    cl = ServingCluster(bank, H)
    out = cl.query_multi(_spread(queries, H))
    out2 = cl.query_multi(_spread(queries, H))
    rows = [r.contained for h in sorted(out) for r in out[h]]
    rows += [r.contained for h in sorted(out2) for r in out2[h]]
    st = cl.router.stats
    batches = sum(h.server.stats["device_batches"] for h in cl.hosts)
    return (np.stack(rows),
            st["l1_hits"] + st["l2_hits"], st["queries"],
            st["shard_batches"], batches)


def test_sampling_changes_no_results_or_dispatches(monkeypatch):
    """The always-on contract at every rate: head sampling at
    0 / 0.3 / 1.0 and tail-only keep must leave query results, cache
    counters and device-dispatch counts bit-identical to tracing
    disabled (sampled roots never block on the device)."""
    db = random_db(5, n_seq=10)
    bank = compile_bank(
        AcceleratedMiner(db).mine_rs(MINSUP, max_len=MAX_LEN))
    if not bank.n_patterns:
        pytest.skip("empty bank for this seed")
    queries = random_db(6, n_seq=6)
    _cluster_run(bank, queries)  # warm the jit buckets
    trace.clear()
    want = _cluster_run(bank, queries)
    assert trace.tracer.events == []  # disabled recorded nothing

    modes = [
        ("head 0%", dict(rate=0.0)),
        ("head 30%", dict(rate=0.3)),
        ("head 100%", dict(rate=1.0)),
        ("tail-only", dict(rate=0.0, latency_threshold=0.0)),
    ]
    blocks = []
    real_block = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocks.append(1) or real_block(x))
    for label, kw in modes:
        reg = MetricsRegistry()
        trace.clear()
        trace.enable_sampling(metrics=reg, **kw)
        got = _cluster_run(bank, queries)
        trace.disable()
        np.testing.assert_array_equal(got[0], want[0],
                                      err_msg=f"[{label}] rows diverged")
        assert got[1:] == want[1:], \
            f"[{label}] counters diverged: {got[1:]} != {want[1:]}"
        snap = reg.snapshot()
        if kw["rate"] >= 1.0 or kw.get("latency_threshold") == 0.0:
            assert snap.get("obs.sampled_spans", 0) > 0, \
                f"[{label}] kept nothing"
        if kw["rate"] == 0.0 and "latency_threshold" not in kw:
            # head sampling kept nothing; only mark()-ed anomalies
            # (e.g. overflow escalation on a toy bank) may remain
            assert all(e.get("args", {}).get("anomaly")
                       for e in trace.tracer.events), \
                f"[{label}] rate-0 sampling kept a non-anomalous root"
        # sampled mode never blocks on the device
        assert blocks == [], f"[{label}] blocked on the device"


def test_sampled_root_records_children_tail_root_does_not():
    reg = MetricsRegistry()
    trace.enable_sampling(1.0, metrics=reg)
    with trace.root_or_span("outer", n=2):
        with trace.span("child", cat="host"):
            pass
    trace.disable()
    names = [e["name"] for e in trace.tracer.events]
    assert names == ["child", "outer"]  # children exit first
    assert reg.counter("obs.sampled_spans").value == 2
    assert reg.counter("obs.sampled_traces").value == 1

    trace.clear()
    reg2 = MetricsRegistry()
    trace.enable_sampling(0.0, latency_threshold=0.0, metrics=reg2)
    with trace.root_or_span("outer"):
        with trace.span("child", cat="host"):
            pass  # nested spans are no-ops on the unsampled path
    trace.disable()
    evs = trace.tracer.events
    assert [e["name"] for e in evs] == ["outer"]
    assert evs[0]["args"]["tail"] is True
    assert reg2.counter("obs.tail_traces").value == 1


def test_systematic_sampler_is_deterministic():
    """rate=0.25 keeps exactly every 4th root - no RNG, so reruns are
    bit-identical (the property the bench's bit-equality gate needs)."""
    trace.enable_sampling(0.25)
    kept = []
    for i in range(12):
        with trace.root_or_span(f"r{i}"):
            pass
        kept.append(len(trace.tracer.events))
    trace.disable()
    assert kept == [0, 0, 0, 1, 1, 1, 1, 2, 2, 2, 2, 3]


def test_mark_keeps_anomalous_roots():
    """``trace.mark`` escalates the active root to always-keep: the
    shed / inexact / overflow paths preserve their traces even when
    head sampling would have dropped them."""
    reg = MetricsRegistry()
    trace.enable_sampling(0.0, metrics=reg)
    with trace.root_or_span("bad"):
        trace.mark("shed")
    with trace.root_or_span("fine"):
        pass
    trace.disable()
    evs = trace.tracer.events
    assert [e["name"] for e in evs] == ["bad"]
    assert evs[0]["args"]["anomaly"] == "shed"
    assert reg.counter("obs.tail_traces").value == 1
    trace.mark("nobody-listening")  # no active root: a silent no-op


# ==================================================== flight recorder
def test_flight_recorder_ring_and_dump(tmp_path):
    from repro.obs import FlightRecorder
    reg = MetricsRegistry()
    now = [100.0]
    fr = FlightRecorder(capacity=3, metrics=reg, metrics_prefix="m",
                        clock=lambda: now[0])
    for i in range(5):
        reg.counter("m.q").inc(10)
        now[0] += 1.0
        fr.record(f"span{i}", 0.25,
                  [{"name": f"span{i}", "cat": "wall",
                    "ts": 0.0, "dur": 250.0, "trace": i}],
                  kind="sampled", trace=i)
    path = str(tmp_path / "flight.jsonl")
    n = fr.dump(path, reason="test")
    assert n == 3  # ring capacity: the oldest two were evicted
    with open(path) as f:
        lines = [json.loads(ln) for ln in f]
    header, entries = lines[0], lines[1:]
    assert header["flight_recorder"] and header["reason"] == "test"
    assert header["total_recorded"] == 5 and header["dropped"] == 2
    assert [e["name"] for e in entries] == ["span2", "span3", "span4"]
    assert all(e["metric_delta"] == {"m.q": 10} for e in entries)
    assert [e["t"] for e in entries] == [103.0, 104.0, 105.0]
    # dump is read-only: a second dump is byte-identical
    path2 = str(tmp_path / "flight2.jsonl")
    fr.dump(path2, reason="test")
    with open(path) as a, open(path2) as b:
        assert a.read() == b.read()


def test_flight_recorder_autodumps_on_anomaly(tmp_path):
    from repro.obs import FlightRecorder
    path = str(tmp_path / "auto.jsonl")
    fr = FlightRecorder(capacity=4, clock=lambda: 1.0,
                        autodump_path=path)
    fr.record("ok", 0.1, [], kind="sampled", trace=1)
    assert not os.path.exists(path)
    fr.record("bad", 0.1, [], kind="tail", trace=2, anomaly="shed")
    assert os.path.exists(path)
    with open(path) as f:
        header = json.loads(f.readline())
    assert header["reason"] == "anomaly:shed"


# ================================================== exporter / prom
def test_prometheus_exposition_roundtrip():
    from repro.obs import prometheus_text, validate_exposition
    reg = MetricsRegistry()
    reg.counter("cluster.router.queries").inc(42)
    reg.gauge("cluster.router.queue_depth").set(3)
    reg.histogram("mining.wavefront.wave_patterns").observe(5.0)
    h = reg.bucket_histogram("cluster.router.e2e_seconds")
    for v in (0.001, 0.01, 0.5):
        h.observe(v)
    text = prometheus_text(reg)
    assert validate_exposition(text) == []
    assert "cluster_router_queries_total 42" in text
    assert 'le="+Inf"} 3' in text
    # the validator is strict: truncating the +Inf bucket line fails
    broken = "\n".join(ln for ln in text.splitlines()
                       if '+Inf' not in ln) + "\n"
    assert validate_exposition(broken)
    # so does a counter sample with no TYPE declaration
    assert validate_exposition("nameless_total 1\n")


def test_metrics_exporter_ships_on_interval(tmp_path):
    from repro.obs import MetricsExporter
    reg = MetricsRegistry()
    reg.counter("m.q").inc(7)
    now = [50.0]
    path = str(tmp_path / "snaps.jsonl")
    exp = MetricsExporter(reg, path, interval=10.0,
                          clock=lambda: now[0])
    assert exp.maybe_ship()        # first call ships immediately
    now[0] += 5.0
    assert not exp.maybe_ship()    # interval not elapsed
    now[0] += 5.0
    reg.counter("m.q").inc(1)
    assert exp.maybe_ship()
    with open(path) as f:
        snaps = [json.loads(ln) for ln in f]
    assert [s["t"] for s in snaps] == [50.0, 60.0]
    assert [s["metrics"]["m.q"] for s in snaps] == [7, 8]


# ========================================================= slo rules
def test_slo_evaluate_kinds():
    from repro.obs import SloRule, evaluate
    rules = [
        SloRule("p99", "quantile", "r.e2e_seconds", 0.5, q=0.99),
        SloRule("shed", "rate", "r.shed", 0.1, den="r.queries"),
        SloRule("depth", "gauge", "r.depth", 4.0),
        SloRule("errors", "counter", "r.errors", 0.0),
    ]
    healthy = {"r.e2e_seconds.p99": 0.2, "r.shed": 1, "r.queries": 100,
               "r.depth": 2, "r.errors": 0}
    assert evaluate(rules, healthy) == []
    sick = {"r.e2e_seconds.p99": 0.9, "r.shed": 30, "r.queries": 100,
            "r.depth": 9, "r.errors": 2}
    assert {b.rule for b in evaluate(rules, sick)} == \
        {"p99", "shed", "depth", "errors"}
    # delta mode: counters/rates look at movement since prev
    prev = dict(sick)
    still = dict(sick, **{"r.e2e_seconds.p99": 0.2, "r.depth": 1})
    assert {b.rule for b in evaluate(rules, still, prev=prev)} == set()
    # an absent histogram / gauge yields no verdict, not a breach
    assert evaluate(rules, {"r.queries": 5}) == []
    with pytest.raises(ValueError):
        SloRule("x", "bogus", "m", 1.0)
    with pytest.raises(ValueError):
        SloRule("x", "rate", "m", 1.0)  # rate without den


def test_watchdog_fires_under_fake_clock(tmp_path):
    """The alarm path, deterministically: a rule breaches -> the
    breach counter moves and the flight recorder dumps with the rule
    names in the reason; ``maybe_check`` honors ``min_interval`` on
    the injected clock."""
    from repro.obs import FlightRecorder, SloRule, SloWatchdog
    reg = MetricsRegistry()
    now = [0.0]
    flight = FlightRecorder(capacity=4, clock=lambda: now[0])
    flight.record("q", 0.1, [], kind="sampled", trace=1)
    dump = str(tmp_path / "slo.jsonl")
    wd = SloWatchdog(
        reg, [SloRule("aging", "gauge", "r.queue_age", 1.0)],
        clock=lambda: now[0], min_interval=5.0, flight=flight,
        dump_path=dump, breach_counter="r.slo_breaches")
    assert wd.maybe_check() == []  # first call checks immediately
    now[0] += 1.0
    reg.gauge("r.queue_age").set(99.0)
    assert wd.maybe_check() is None  # rate-limited
    assert reg.counter("r.slo_breaches").value == 0
    now[0] += 5.0
    breaches = wd.maybe_check()
    assert [b.rule for b in breaches] == ["aging"]
    assert reg.counter("r.slo_breaches").value == 1
    with open(dump) as f:
        header = json.loads(f.readline())
    assert header["reason"] == "slo:aging"
    assert wd.checks == 2
