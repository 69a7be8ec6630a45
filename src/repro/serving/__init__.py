"""Query-time pattern serving: from ``MiningResult`` to production
containment queries.

Mining (repro.mining) produces the rFTS bank; this package answers the
deployment-side question - "which mined patterns does this incoming
graph sequence contain?" - as a batched device computation instead of a
per-sequence host backtrack.  The mining layer mirrors the same
batching discipline on the producer side: ``mining.driver``'s wavefront
scheduler packs the embeddings of many frontier patterns into shared
device scans (one dispatch per chunk, not per pattern - see driver.py's
docstring), and ``mining.incremental``'s frontier re-mine - the engine
behind ``StreamingBank.refresh()`` and the sharded-window reconcile -
drains its dirty frontier through the same batched expansion.

Module map:

* ``bank.py``    - compile a ``MiningResult`` into a packed pattern bank
                   (per-pattern int32 step programs + support/metadata
                   rows) and renaming-invariant canonical sequence
                   fingerprints (cache keys that hit for any vertex
                   bijection of a previously served sequence).
* ``trie.py``    - the prefix-trie re-layout of a bank: mined rFTSs are
                   nodes of the reverse-search spanning tree and share
                   program prefixes, so the trie stores each distinct
                   prefix once (LCP merging; one node per step row) and
                   carries per-node residual ``node_req`` prescreen rows
                   (min over the subtree's terminals) that prune whole
                   subtrees at their highest failing ancestor.  See its
                   docstring for when to prefer flat vs trie.
* ``batch.py``   - the jitted embedding-join scans: the flat
                   per-(sequence, pattern) layout (dense
                   ``batch_contains``, prescreen-compacted
                   ``pair_contains``) and the trie layout
                   (level-synchronous ``trie_contains`` /
                   ``trie_level_advance``, one frontier per
                   (sequence, trie node) seeded from its parent's
                   compacted frontier - bit-identical answers, shared
                   prefixes joined once); ``fused_trie_walk``, the jit
                   wrapper over ``repro.kernels.trie_walk`` that runs
                   the whole walk (all levels, in-kernel frontier
                   buffers + per-node prescreen) in ONE dispatch
                   gridded over (sequence, depth-1 subtree shard) -
                   see ``trie.pack_subtrees`` for the width-capped
                   spine-replicated shard layout; plus the sound
                   counts prescreens, inverted token index, frontier
                   compaction and overflow flags.  Delegates the
                   per-step predicate to ``repro.kernels.containment``
                   (Pallas kernel or jnp oracle).
* ``layouts.py`` - the ``Layout`` registry: each bank layout
                   (``"flat"``, ``"trie"``, ``"trie_fused"``) registers
                   its launch/finalize/shard hooks once and every
                   consumer (server, placement planner, CLI) resolves
                   by name via ``get_layout`` - adding a layout no
                   longer touches server/router/cluster plumbing.
* ``join.py``    - the unified Join API: ``JoinRequest -> JoinResult``
                   is the one protocol every backend speaks
                   (``PatternServer``, ``ClusterRouter``,
                   ``ServingCluster``, ``StreamingBank``); the legacy
                   entry points survive as thin wrappers.  ``Frontend``
                   is the backend-agnostic facade, including the
                   begin/finish split over async pipelines.  Exactness
                   propagation (``exact=False`` on every approximate
                   row) is part of the protocol.
* ``server.py``  - ``PatternServer``: request batching into pow-2
                   buckets, prescreen + join under any registered
                   ``bank_layout``, fingerprint-keyed LRU cache,
                   support-weighted top-k scoring, device escalation +
                   host-oracle fallback for overflow cells (results
                   always exactly match ``core.containment``); plus the
                   streaming layer's hooks - ``exact_rows`` (chunked,
                   cache-bypassing rows) and ``set_row_mask`` (tombstone
                   masking via ``REQ_MASKED`` prescreen rows).
* ``streaming.py`` - ``StreamingBank``: incremental support maintenance
                   over a sliding window.  Arrivals are counted by the
                   device containment join, expiries decremented from a
                   ring buffer of per-sequence containment bitmaps (no
                   re-join on eviction); sub-``minsup`` patterns are
                   tombstoned (prescreen-masked, trie subtrees pruned);
                   ``refresh()`` reconciles incrementally via the
                   frontier re-mine (``mining.incremental``), extending
                   the bank/trie in place, with ``refresh(full=True)``
                   as the re-mine-everything escape hatch.  After a
                   refresh the frequent map is bit-equal to a batch
                   re-mine of the window.
* ``sharded.py`` - shard-by-pattern (flat) / shard-by-subtree (trie)
                   serving steps for device meshes (zero-collective
                   shard_map).
* ``router.py``  - the cluster query plane: bank placement across hosts
                   (intact depth-1 trie subtrees / flat pattern ranges)
                   and ``ClusterRouter`` - queries arriving on any host
                   are deduped by canonical fingerprint, resolved
                   through the two-level cache (host-local L1,
                   fingerprint-owner L2), and the misses batched into
                   shared pow-2 device batches per shard; merged
                   answers are bit-equal to a single-host server.
                   Two driving modes share that machinery: synchronous
                   ``route`` (one blocking drain) and the async
                   admission pipeline ``submit -> [queue] -> flush ->
                   [in-flight] -> collect`` (continuous batching: the
                   queries are encoded once per flush, every shard's
                   join launches before any is fenced, and later
                   drains keep admitting while earlier batches compute
                   on device; repeats piggyback on queued/in-flight
                   joins).  Flushes trigger on queue length
                   (``flush_batch``), head-of-queue age (``max_wait``,
                   against an injectable clock), or a blocked
                   ``collect``; past ``shed_depth`` new misses get
                   host-prescreen-only answers - sound supersets
                   flagged ``exact=False``, never cached (off by
                   default: exactness stays the contract).
* ``cluster.py`` - the multi-host topologies over router.py:
                   ``ServingCluster`` (static sharded bank),
                   ``ShardedStreamingBank`` (the sharded-window
                   protocol: per-host ring slices + partial supports,
                   all-reduced with a depth-1-subtree dirtiness index
                   at ``refresh()``), and ``ReplicaGroup`` (single
                   writer shipping ``extend_bank``/``extend_trie``
                   deltas to read replicas).  Hosts are an abstraction
                   (in-process simulated hosts, optionally device-
                   pinned), so every protocol is property-tested
                   bit-equal to its single-host counterpart.
* ``faults.py``  - the failure model: ``FaultInjector`` (a seeded,
                   deterministic schedule of delays, transient errors
                   and host blackout windows at the ``ClusterHost.call``
                   boundary - no RNG at query time, so any chaos run
                   replays bit-identically), ``RetryPolicy`` (per-call
                   timeouts, capped exponential backoff, the
                   consecutive-failure circuit breaker), the typed
                   fault hierarchy (``HostFault`` and friends,
                   ``HostUnavailableError``, ``PipelineBusyError``),
                   and ``RecoveryLog`` (the writer-side sequenced
                   delta ring that replays a restarted replica back to
                   bit-equal state).

Fault tolerance (``serving.faults``, the failure model): every
cross-host access already flows through ``ClusterHost.call``, so the
fault seam is one boundary.  With a ``RetryPolicy`` armed the router
wraps every host call in per-call timeouts + capped-backoff retries;
``breaker_threshold`` consecutive failures open a per-host circuit
breaker (open -> short-circuit without touching the host -> half-open
single probe after ``breaker_cooldown`` -> close with wiped caches on
success).  While a host is down its column block degrades down a
two-rung ladder: a registered failover replica
(``ServingCluster.attach_failover_replica``) serves bit-equal
``exact=True`` rows; otherwise the router answers from the host-side
prescreen mirror - a sound superset flagged ``exact=False`` (the shed
tier's protocol), never cached.  ``collect(timeout=...)`` bounds the
async drain the same way: past the deadline stragglers degrade instead
of blocking.  Strict entry points (``joined_rows``/``exact_rows``)
refuse with ``HostUnavailableError`` rather than degrade.  Streaming
deltas carry monotone sequence ids; a crashed replica restarts by
replaying the writer's ``RecoveryLog`` from its last applied seq
(verified bit-equal catch-up, full resync when the ring evicted the
gap).  The whole ladder is off by default and the idle-injector run is
property-tested bit-identical to the pre-fault cluster
(tests/test_faults.py); ``benchmarks/bench_faults.py`` gates
availability >= 0.99 with one of four hosts blacked out and zero
unflagged-inexact answers.  Counters:
``cluster.faults.{injected, retries, breaker_open, failovers,
degraded_answers, recoveries}`` + the ``cluster.faults.retry_seconds``
histogram; faulted calls ``trace.mark("host_fault")`` so sampled
traces keep them.

Observability (``repro.obs``, cross-cutting): every layer's counters
live in a ``MetricsRegistry`` (``server.stats``, ``router.stats``, the
streaming banks' ``stats`` are ``StatsView`` facades over it), so
counters survive component rebuilds - a ``refresh(full=True)`` that
recompiles the server or re-plans the router re-attaches by name and
keeps accumulating; ``registry.snapshot()/delta()`` feed the BENCH
artifacts' ``metrics`` blocks.  The admission pipeline adds
``cluster.router.{inflight_hits, shed_prescreen, flush_batch,
flush_deadline, flush_force}`` counters and the
``cluster.router.queue_depth`` gauge (queued + un-fenced in-flight
misses); per-shard servers count every join entry point under
``serving.server.h<hid>.queries``.  The span tracer (``repro.obs.trace``)
threads one trace id per routed query / wavefront through
``ClusterRouter.route -> ClusterHost.call -> PatternServer -> kernel
dispatch``; it is off by default and property-tested to change nothing
(tests/test_obs.py).  Render a saved trace with
``scripts/trace_report.py``.  While a JAX profiler session runs, the
same spans also land on the profiler's host plane, on the device
trace's clock: ``serving.fingerprint`` (the renaming-invariant
fingerprints of a submit or route), ``cluster.flush`` with
``serving.encode``, ``serving.token_index``, ``serving.batch``,
``serving.prescreen_host`` and ``serving.join`` (launches: each ends
when the async call returns), and ``cluster.fence`` with
``serving.finalize_rows``, ``serving.readback`` (the layout's read of
the join outputs), ``serving.escalate`` (the escalation ladder) and
``serving.oracle`` (the host fallback inside it).  No mode blocks on
the device to time it: device time is read from the profiler's device
planes.

**Always-on production telemetry** (cheap enough to leave on; the
benches gate the measured sampled-mode overhead <= 5%):

* **Latency percentiles** - ``BucketHistogram`` (fixed log-scale
  buckets, constant memory, exact quantile bounds) records per-query
  end-to-end latency and queue time at every admission seam:
  ``serving.{flat,trie,fused}.query_seconds``,
  ``serving.*.batch_seconds``, ``cluster.router.{e2e_seconds,
  queue_wait_seconds, flush_seconds, route_seconds}``,
  ``streaming.{bank,sharded}.{observe,refresh}_seconds``,
  ``mining.*.wave_seconds``; plus the ``cluster.router.{queue_age,
  oldest_ticket_age}`` aging gauges.  Snapshots expose
  ``<name>.p50/.p95/.p99``.
* **Sampled tracing** - ``trace.enable_sampling(rate)`` keeps every
  ``1/rate``-th root span *tree* (deterministic systematic sampler -
  no RNG, so results stay bit-reproducible) plus every tail root that
  breaches ``latency_threshold`` or was ``trace.mark()``-ed anomalous
  (shed, inexact, overflow-escalated).  Like full ``enable()`` it
  never blocks on the device - launch spans end when the call
  returns, so the async pipeline keeps its overlap.  Keeps count under
  ``obs.{sampled_spans, sampled_traces, tail_traces}``.
* **Flight recorder** - ``FlightRecorder`` rings the last N kept
  traces with per-entry metric deltas; dumped as JSONL on demand, on
  an anomalous entry, or by the watchdog on an SLO breach.
* **SLO watchdog** - declarative rules (``scripts/slo_rules.json``:
  quantile / rate / gauge / counter bounds) evaluated by
  ``SloWatchdog`` riding ``ClusterRouter._note_depth`` (attach via
  ``ServingCluster.attach_watchdog``); breaches increment
  ``cluster.router.slo_breaches`` and trigger a flight dump.  The
  same rules file drives the ``scripts/trace_report.py --slo`` CI
  gate against BENCH metrics blocks.
* **Export** - ``prometheus_text`` / ``validate_exposition`` (strict
  0.0.4 text exposition) and ``MetricsExporter`` (periodic JSONL
  snapshots on an injectable clock).
"""
from .bank import (  # noqa: F401
    BankCapacityError,
    PatternBank,
    canonical_sequence_map,
    compile_bank,
    extend_bank,
    sequence_fingerprint,
    slice_bank,
)
from .batch import (  # noqa: F401
    batch_contains,
    index_and_node_prescreen,
    index_and_prescreen,
    max_key_bucket,
    pair_contains,
    pair_contains_indexed,
    prescreen_counts,
    trie_contains,
    trie_level_advance,
)
from .cluster import (  # noqa: F401
    BankReplica,
    ClusterHost,
    ReplicaGroup,
    ServingCluster,
    ShardedStreamingBank,
)
from .faults import (  # noqa: F401
    FaultInjector,
    HostDownError,
    HostFault,
    HostTimeoutError,
    HostUnavailableError,
    PipelineBusyError,
    RecoveryLog,
    RetryPolicy,
    TransientHostError,
)
from .join import (  # noqa: F401
    Frontend,
    JoinRequest,
    JoinResult,
)
from .layouts import (  # noqa: F401
    Layout,
    get_layout,
    layout_names,
    register_layout,
)
from .router import (  # noqa: F401
    BankPlacement,
    ClusterRouter,
    DrainTicket,
    plan_placement,
)
from .server import (  # noqa: F401
    PatternServer,
    QueryResult,
    SharedEncoding,
    encode_queries,
)
from .sharded import (  # noqa: F401
    make_serving_step,
    make_trie_serving_step,
    stack_trie_shards,
)
from .streaming import ObserveResult, StreamingBank  # noqa: F401
from .trie import (  # noqa: F401
    SubtreePack,
    TrieBank,
    build_trie,
    compile_trie_bank,
    extend_trie,
    masked_node_req,
    pack_subtrees,
    parent_prefix_hits,
)
