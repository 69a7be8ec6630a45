"""Accelerated miners: host frontier + wavefront-batched device scans.

The reverse-search frontier (tiny, independent subtrees) stays on the
host; every DB scan - the >95% hot loop - is a batched device call to
the embedding-join engine.  Outputs are bit-identical to the pure-host
reference miners in ``repro.core`` (property-tested).

The wavefront scheduler
-----------------------
Reverse search makes enumeration subtrees independent, so nothing
orders the pending expansions: any set of frontier patterns can be
scanned together.  The default ``dispatch="wavefront"`` exploits that:
the work pool is drained in *slices* of many patterns at once, their
embeddings are packed into shared pow-2-bucketed device batches with a
per-row ``pattern_id`` axis (stacked ``existing`` tables and per-row
``nv``/``n_pat``/``mode`` vectors, gathered inside the jit - see
``engine.match_signatures_batch``), and ONE dispatch covers the whole
chunk instead of one per pattern.  Signatures come back namespaced by
``pattern_id`` (``engine.aggregate_host_batch``), so the host finalize
splits per pattern exactly as before.  ``dispatch="pattern"`` keeps
the seed's one-pattern-at-a-time traversal (same code path, slices of
size one) as the benchmark baseline; both dispatch modes return
bit-equal ``MiningResult``s.

The frontier is encoded end to end: a pending item's embeddings are the
``Enc`` triple (``gid`` [E], ``phi`` [E, ni] padded with ``PAD_PHI``,
``psi`` [E, nv] padded with ``PAD_PSI``) that the next scan packs as it
is.  A kept child's rows are rebuilt from its parent's ``Enc`` with
numpy scatter/stack ops over the whole (e,t) row set, deduped, and
padded straight into a new ``Enc``; no Python runs per row between a
chunk's readback and the next dispatch.  ``Emb`` tuples appear only at
``expand_children`` (the single-item public helper, which decodes its
result) and at checkpoints; a ``List[Emb]`` handed in is encoded when
its slice is packed.

A wavefront is just a reordered work stack, so the miner stays
checkpointable (see checkpoint.py): the pending slice items plus the
accumulated next wave serialize exactly like the seed stack, and a
resume re-enqueues them - supports are per-subtree and idempotent.  A
checkpoint decodes the pending items to ``Emb`` lists (the wire format
is unchanged) and a resume encodes them again.

Device timing: jax dispatch is async, so the launch and the execution
are timed separately - ``dispatch_seconds`` stops when the call
returns (launch cost only), ``device_seconds`` after
``block_until_ready()`` (launch plus the wait; the host reads the
signatures right after in any case).

Host phases (``repro.obs.trace`` spans, which reach the JAX profiler's
trace whenever a session runs): ``mining.pack`` (the pattern stacks
and the items' row arrays concatenated, once per slice),
``mining.scan`` (one chunk's dispatch and wait), ``mining.aggregate``
(its signatures read back and merged), and per item
``mining.children`` around ``mining.prescreen`` (the signature ->
extension -> isomorphism-invariant grouping),
``mining.canonical`` (the canonical-form grouping of the surviving
signatures), ``mining.parent`` (the reverse-search membership test,
once per frequent candidate) and ``mining.rebuild`` (one kept child's
embeddings; ``rows=`` the (e,t) rows it reads).  The counter
``rebuild_rows`` counts the rows the rebuilds emit, the frontier the
job carries.

The prescreen is a support bound: every signature of one canonical
child lies in one invariant group (``core.canonical.iso_invariant``),
and the child's support is the union over a subset of that group, so
a group whose union of supporting sequences is below sigma holds only
infrequent children and no canonical form is computed for it.  The
counters ``signatures`` (past the vertex-capacity guard) and
``canon_skipped`` (those whose canonical form was skipped) say how
often it engages.  ``candidates``, ``infrequent`` and ``rs_rejected``
count the funnel those phases walk: candidates (distinct canonical
children, plus one per pruned invariant group), those below sigma
(pruned groups included), and those whose parent is another pattern;
``candidates == infrequent + rs_rejected + n_enumerated``.
"""
from __future__ import annotations

import math
import time
from collections import deque
from typing import (
    Callable, Dict, List, Optional, Sequence, Set, Tuple, Union,
)

import jax.numpy as jnp
import numpy as np

from ..core.canonical import canonical_form, canonical_map, iso_invariant
from ..obs import trace
from ..obs.metrics import MetricsRegistry
from ..core.enumerate_host import Emb, apply_extension
from ..core.gtrace import MiningResult
from ..core.graphseq import Pattern, TRSeq, pattern_length, pattern_vertices
from ..core.reverse_search import parent
from .encoding import (
    PAD_PHI,
    PAD_PSI,
    Enc,
    TokenDB,
    decode_embeddings,
    encode_db,
    encode_embeddings,
    encode_pattern_trs,
    signature_to_extkey,
)
from .engine import (
    MODE_EDGE_PHASE,
    MODE_ROOT,
    MODE_TAIL,
    MODE_VERTEX_PHASE,
    aggregate_host_batch,
    match_signatures_batch,
)

MAX_PATTERN_TRS = 64

# a frontier item: a pattern and its embedding rows, encoded
Item = Tuple[Pattern, Enc]


def _pow2_pad(n: int, cap: Optional[int] = None) -> int:
    """Smallest power of two >= n (clamped to cap when given, but never
    below n) - bounds the set of jit shapes."""
    p = 1 << max(0, math.ceil(math.log2(max(n, 1))))
    if cap is not None:
        p = min(p, cap)
    return max(p, n)


class AcceleratedMiner:
    def __init__(
        self,
        db: Sequence[TRSeq],
        max_itemsets: int = 16,
        max_vertices: int = 12,
        e_batch: int = 1024,
        dispatch: str = "wavefront",
        wave_patterns: int = 256,
        wave_rows: Optional[int] = None,
        metrics: Optional[MetricsRegistry] = None,
        metrics_ns: str = "mining",
    ):
        assert dispatch in ("wavefront", "pattern"), dispatch
        self.db = db
        self.ni = max_itemsets
        self.nv = max_vertices
        self.e_batch = e_batch
        self.dispatch = dispatch
        # wavefront slice bounds: at most this many patterns / embedding
        # rows per batched expansion (the checkpoint granularity; pow-2
        # padding of the pattern axis keeps jit shapes bounded)
        self.wave_patterns = wave_patterns
        self.wave_rows = 4 * e_batch if wave_rows is None else wave_rows
        self.tdb: TokenDB = encode_db(db)
        self.tokens = jnp.asarray(self.tdb.tokens)
        # the rows of a child whose embeddings were not rebuilt
        self._no_rows: Enc = (
            np.zeros((0,), np.int32),
            np.zeros((0, self.ni), np.int32),
            np.zeros((0, self.nv), np.int32),
        )
        # counters live in a registry (private by default; pass
        # ``metrics=`` to accumulate across miner rebuilds, e.g. the
        # streaming bank's incremental refreshes)
        self.metrics = MetricsRegistry() if metrics is None else metrics
        self._c_device_s = self.metrics.counter(
            f"{metrics_ns}.device_seconds")
        self._c_dispatch_s = self.metrics.counter(
            f"{metrics_ns}.dispatch_seconds")
        self._c_calls = self.metrics.counter(
            f"{metrics_ns}.n_device_calls")
        self._h_wave = self.metrics.histogram(
            f"{metrics_ns}.wave_patterns")
        # the reverse-search funnel: candidates (distinct canonical
        # children, one per pruned invariant group), those below sigma,
        # and those that failed ``parent(child) == pattern``
        self._c_candidates = self.metrics.counter(
            f"{metrics_ns}.candidates")
        self._c_infrequent = self.metrics.counter(
            f"{metrics_ns}.infrequent")
        self._c_rs_rejected = self.metrics.counter(
            f"{metrics_ns}.rs_rejected")
        # the prescreen: signatures past the capacity guard, and those
        # whose canonical form the support bound skipped
        self._c_signatures = self.metrics.counter(
            f"{metrics_ns}.signatures")
        self._c_canon_skipped = self.metrics.counter(
            f"{metrics_ns}.canon_skipped")
        # embedding rows the rebuilds emit (the frontier carried)
        self._c_rebuild_rows = self.metrics.counter(
            f"{metrics_ns}.rebuild_rows")
        # always-on latency percentiles: wall (launch + blocked) per
        # packed device chunk, log-scale buckets
        self._h_wave_s = self.metrics.bucket_histogram(
            f"{metrics_ns}.wave_seconds")

    # registry-backed views of the historical timing attributes
    @property
    def device_seconds(self) -> float:
        """Launch + execution (blocked)."""
        return self._c_device_s.value

    @property
    def dispatch_seconds(self) -> float:
        """Async launch only."""
        return self._c_dispatch_s.value

    @property
    def n_device_calls(self) -> int:
        return self._c_calls.value

    # ------------------------------------------------------------- phases
    @staticmethod
    def _phase_mode(pattern: Pattern, rs: bool) -> int:
        if not rs:
            return MODE_TAIL
        if not pattern:
            return MODE_ROOT
        if any(tr.is_vertex for s in pattern for tr in s):
            return MODE_VERTEX_PHASE
        return MODE_EDGE_PHASE

    # ------------------------------------------------------------- scans
    def _encoded(self, embs: Union[Enc, List[Emb]]) -> Enc:
        """An item's rows as ``Enc``: the frontier's own arrays as they
        are, a ``List[Emb]`` (an outside caller's) encoded."""
        if (isinstance(embs, tuple) and len(embs) == 3
                and isinstance(embs[0], np.ndarray)):
            return embs
        return encode_embeddings(embs, self.ni, self.nv)

    def _root_item(self) -> Item:
        """The empty pattern, embedded once in every sequence."""
        G = len(self.db)
        return ((), (
            np.arange(G, dtype=np.int32),
            np.full((G, self.ni), PAD_PHI, np.int32),
            np.full((G, self.nv), PAD_PSI, np.int32),
        ))

    def _scan_batch(
        self, items: List[Tuple[Pattern, Union[Enc, List[Emb]]]],
        modes: List[int],
    ) -> Tuple[List[Dict[int, Tuple[Set[int], List[np.ndarray]]]],
               List[Enc]]:
        """Run the device scans for a wavefront slice: all items' rows
        are packed into shared pow-2 chunks (a chunk freely spans
        pattern boundaries) and each chunk is ONE device dispatch.
        Returns, per item, ``{sig: (gid_set, (e,t) rows)}`` with ``e``
        local to the item's embedding rows, plus the item's ``Enc`` for
        the vectorized embedding rebuild."""
        n = len(items)
        merged: List[Dict[int, Tuple[Set[int], List[np.ndarray]]]] = [
            {} for _ in items
        ]
        with trace.span("mining.pack", patterns=n):
            n_pad = _pow2_pad(n)
            nv_stack = np.zeros(n_pad, np.int32)
            npat_stack = np.zeros(n_pad, np.int32)
            mode_stack = np.zeros(n_pad, np.int32)
            ex_stack = np.full((n_pad, MAX_PATTERN_TRS, 5), -9, np.int32)
            for i, (pattern, _) in enumerate(items):
                nv_stack[i] = len(pattern_vertices(pattern))
                npat_stack[i] = len(pattern)
                mode_stack[i] = modes[i]
                ex_stack[i] = encode_pattern_trs(pattern, MAX_PATTERN_TRS)
            ex_j = jnp.asarray(ex_stack)
            nv_j = jnp.asarray(nv_stack)
            npat_j = jnp.asarray(npat_stack)
            mode_j = jnp.asarray(mode_stack)

            enc = [self._encoded(embs) for _, embs in items]
            lens = np.asarray([len(e[0]) for e in enc], np.int64)
            offs = np.cumsum(lens) - lens
            R = int(lens.sum())
            if R == 0:
                return merged, enc
            gid_all = np.concatenate([e[0] for e in enc])
            phi_all = np.concatenate([e[1] for e in enc])
            psi_all = np.concatenate([e[2] for e in enc])
            pid_all = np.repeat(np.arange(n, dtype=np.int32), lens)

        for start in range(0, R, self.e_batch):
            E = min(self.e_batch, R - start)
            Epad = _pow2_pad(E, cap=self.e_batch)
            with trace.span("mining.scan", rows=Epad):
                sl = slice(start, start + E)
                gid = gid_all[sl]
                phi = phi_all[sl]
                psi = psi_all[sl]
                pid = pid_all[sl]
                if Epad > E:
                    gid = np.pad(gid, (0, Epad - E))
                    phi = np.pad(phi, ((0, Epad - E), (0, 0)),
                                 constant_values=PAD_PHI)
                    psi = np.pad(psi, ((0, Epad - E), (0, 0)),
                                 constant_values=PAD_PSI)
                    pid = np.pad(pid, (0, Epad - E))
                valid = np.zeros((Epad,), np.int32)
                valid[:E] = 1
                t0 = time.perf_counter()
                sigs = match_signatures_batch(
                    self.tokens,
                    jnp.asarray(gid), jnp.asarray(phi), jnp.asarray(psi),
                    jnp.asarray(valid), jnp.asarray(pid),
                    ex_j, nv_j, npat_j, mode_j,
                )
                t1 = time.perf_counter()
                self._c_dispatch_s.inc(t1 - t0)
                # async dispatch: launch != done; the host reads the
                # signatures right after, so this wait costs nothing
                sigs.block_until_ready()
                t2 = time.perf_counter()
                self._c_device_s.inc(t2 - t0)
                self._c_calls.inc()
                self._h_wave_s.observe(t2 - t0)
            with trace.span("mining.aggregate", rows=Epad):
                for (pi, sig), (gset, et) in aggregate_host_batch(
                    np.asarray(sigs), gid, pid
                ).items():
                    et = et.copy()
                    # chunk-local row -> this item's embedding index
                    et[:, 0] += start - offs[pi]
                    got = merged[pi].get(sig)
                    if got is None:
                        merged[pi][sig] = (gset, [et])
                    else:
                        got[0].update(gset)
                        got[1].append(et)
        return merged, enc

    # -------------------------------------------------- embedding rebuild
    def _rebuild_embeddings(
        self,
        pattern: Pattern,
        enc: Enc,
        sig: int,
        et_rows: List[np.ndarray],
        child_raw: Pattern,
    ) -> Enc:
        """Vectorized child-embedding rebuild, from the parent's ``Enc``
        to the child's: phi insertion, the psi variant construction,
        canonical remap, first-seen dedup and the padding to ``ni`` /
        ``nv`` columns are numpy column ops over the whole (e,t) row set
        (the extension key - and therefore the variant case - is
        constant per signature).  No Python runs per row; the result is
        what ``encode_embeddings`` makes of the deduped rows as ``Emb``
        tuples, bit for bit and in the same order."""
        (slot_kind, slot_idx), ptr = signature_to_extkey(sig)
        nv = len(pattern_vertices(pattern))
        n_pat = len(pattern)
        vmap = canonical_map(child_raw)
        gid_all, phi_all, psi_all = enc
        et = np.concatenate(et_rows, axis=0)
        e_i, t_i = et[:, 0], et[:, 1]
        gids_r = gid_all[e_i].astype(np.int64)
        tok = self.tdb.tokens[gids_r, t_i]
        u1, u2, j = tok[:, 1], tok[:, 2], tok[:, 4]
        phi_r = phi_all[e_i]
        if slot_kind == "in":
            new_phi = phi_r[:, :n_pat]
        else:
            new_phi = np.concatenate(
                [phi_r[:, :slot_idx], j[:, None],
                 phi_r[:, slot_idx:n_pat]], axis=1)
        psi_r = psi_all[e_i][:, :nv]
        if ptr.is_vertex:
            if ptr.u1 == nv:  # fresh vertex
                psis = [np.concatenate([psi_r, u1[:, None]], axis=1)]
            else:
                psis = [psi_r]
        elif ptr.u2 == nv + 1:  # both endpoints fresh: two bindings
            psis = [
                np.concatenate([psi_r, u1[:, None], u2[:, None]], axis=1),
                np.concatenate([psi_r, u2[:, None], u1[:, None]], axis=1),
            ]
        elif ptr.u2 == nv:  # one fresh endpoint
            mapped_dv = psi_r[:, ptr.u1]
            fresh_dv = np.where(mapped_dv == u1, u2, u1)
            psis = [np.concatenate([psi_r, fresh_dv[:, None]], axis=1)]
        else:
            psis = [psi_r]
        nv_child = psis[0].shape[1]
        perm = np.asarray([vmap[pv] for pv in range(nv_child)])
        n_phi = new_phi.shape[1]
        variants = []
        for ps in psis:
            canon = np.empty_like(ps)
            canon[:, perm] = ps  # scatter into canonical vertex order
            variants.append(np.concatenate(
                [gids_r[:, None], new_phi, canon], axis=1))
        if len(variants) == 2:  # interleave bindings per row
            rows = np.stack(variants, axis=1).reshape(
                2 * len(et), 1 + n_phi + nv_child)
        else:
            rows = variants[0]
        _, first = np.unique(rows, axis=0, return_index=True)
        rows = rows[np.sort(first)]  # dedup, first-seen order
        R = len(rows)
        phi = np.full((R, self.ni), PAD_PHI, np.int32)
        phi[:, :n_phi] = rows[:, 1:1 + n_phi]
        psi = np.full((R, self.nv), PAD_PSI, np.int32)
        psi[:, :nv_child] = rows[:, 1 + n_phi:]
        return rows[:, 0].astype(np.int32), phi, psi

    # -------------------------------------------------- child expansion
    def _children_from_merged(
        self,
        pattern: Pattern,
        enc: Enc,
        merged: Dict[int, Tuple[Set[int], List[np.ndarray]]],
        min_support: int,
        rs: bool,
        want_embs: Optional[Callable[[Pattern], bool]],
    ) -> List[Tuple[Pattern, Set[int], Enc]]:
        # support-bound prescreen: group by the isomorphism invariant;
        # a group whose union of gids is below sigma holds only
        # infrequent children, so none of it is canonicalized.  Each raw
        # entry: (sig, child_raw, its group's gid union, gset, et_rows)
        raw: List[Tuple[int, Pattern, Set[int], Set[int],
                        List[np.ndarray]]] = []
        groups: Dict[tuple, Set[int]] = {}
        with trace.span("mining.prescreen", signatures=len(merged)):
            for sig, (gset, et_rows) in merged.items():
                key = signature_to_extkey(sig)
                if max(key[1].u1, key[1].u2) >= self.nv:
                    continue  # vertex-capacity guard
                child_raw = apply_extension(pattern, key)
                inv = iso_invariant(child_raw)
                group = groups.get(inv)
                if group is None:
                    group = groups[inv] = set(gset)
                else:
                    group.update(gset)
                raw.append((sig, child_raw, group, gset, et_rows))
        pruned = sum(len(g) < min_support for g in groups.values())
        by_child: Dict[Pattern, Tuple[Set[int], int, Pattern,
                                      List[np.ndarray]]] = {}
        skipped = 0
        with trace.span("mining.canonical", signatures=len(raw)):
            for sig, child_raw, group, gset, et_rows in raw:
                if len(group) < min_support:
                    skipped += 1
                    continue
                child = canonical_form(child_raw)
                if child in by_child:
                    by_child[child][0].update(gset)
                else:
                    by_child[child] = (set(gset), sig, child_raw, et_rows)
        out: List[Tuple[Pattern, Set[int], Enc]] = []
        infrequent = rejected = rebuilt_rows = 0
        for child, (gids, sig, child_raw, et_rows) in by_child.items():
            if len(gids) < min_support:
                infrequent += 1
                continue
            if rs:
                # reverse-search membership test
                with trace.span("mining.parent"):
                    is_child = parent(child) == pattern
                if not is_child:
                    rejected += 1
                    continue
            if want_embs is not None and not want_embs(child):
                out.append((child, gids, self._no_rows))
                continue
            with trace.span("mining.rebuild",
                            rows=sum(map(len, et_rows))):
                child_enc = self._rebuild_embeddings(
                    pattern, enc, sig, et_rows, child_raw
                )
            rebuilt_rows += len(child_enc[0])
            out.append((child, gids, child_enc))
        self._c_signatures.inc(len(raw))
        self._c_canon_skipped.inc(skipped)
        self._c_candidates.inc(len(by_child) + pruned)
        self._c_infrequent.inc(infrequent + pruned)
        self._c_rs_rejected.inc(rejected)
        self._c_rebuild_rows.inc(rebuilt_rows)
        return out

    def expand_children_batch(
        self,
        items: Sequence[Tuple[Pattern, Union[Enc, List[Emb]]]],
        min_support: int,
        *,
        rs: bool = True,
        want_embs: Optional[Callable[[Pattern], bool]] = None,
    ) -> List[List[Tuple[Pattern, Set[int], Enc]]]:
        """One batched expansion of a whole wavefront slice: every
        item's DB scan shares the packed device chunks (see
        ``_scan_batch``); the result is per-item, aligned with
        ``items``, each entry what ``expand_children`` would have
        returned for that item alone but with each child's embeddings
        as an ``Enc`` (``decode_embeddings`` gives the ``Emb`` list; a
        child whose rebuild ``want_embs`` skipped has zero rows).  An
        item's embeddings may come as an ``Enc`` (the frontier's own)
        or as a ``List[Emb]``.  Items at the itemset capacity come back
        empty (same guard as the single-item path)."""
        out: List[List[Tuple[Pattern, Set[int], Enc]]] = [
            [] for _ in items
        ]
        live = [
            (i, p, e) for i, (p, e) in enumerate(items)
            if len(p) < self.ni
        ]
        if not live:
            return out
        modes = [self._phase_mode(p, rs) for _, p, _ in live]
        merged, enc = self._scan_batch([(p, e) for _, p, e in live], modes)
        for (i, p, _), m, enc_i in zip(live, merged, enc):
            with trace.span("mining.children"):
                out[i] = self._children_from_merged(
                    p, enc_i, m, min_support, rs, want_embs
                )
        return out

    def expand_children(
        self,
        pattern: Pattern,
        embs: List[Emb],
        min_support: int,
        *,
        rs: bool = True,
        want_embs: Optional[Callable[[Pattern], bool]] = None,
    ) -> List[Tuple[Pattern, Set[int], List[Emb]]]:
        """One reverse-search (or baseline tail-growth) expansion: scan
        the DB for one-TR extensions of ``pattern`` and return its
        frequent children as ``(child, gids, child_embs)``.  ``gids`` is
        the exact set of DB sequences containing the child (supports are
        ``len(gids)``; the streaming layer turns these into window
        containment bitmaps without a separate join).

        With ``rs=True`` children are filtered by the spanning-tree
        membership test (``parent(child) == pattern``) exactly as the
        full miner does, so iterating this from the root reproduces
        ``mine_rs`` - and iterating it from a *frontier* of known
        patterns is the incremental re-mine (mining.incremental; batch
        the frontier through ``expand_children_batch`` to share device
        chunks across patterns).  ``want_embs(child)`` lets callers skip
        the embedding rebuild for children whose subtree they will not
        descend into (the clean-subtree prune); such children come back
        with ``[]``.  Respects the miner's itemset/vertex capacity
        guards."""
        return [
            (child, gids, decode_embeddings(enc))
            for child, gids, enc in self.expand_children_batch(
                [(pattern, embs)], min_support, rs=rs, want_embs=want_embs
            )[0]
        ]

    # ------------------------------------------------------------ mining
    def _take_slice(
        self,
        pending: "deque[Item]",
        max_len: Optional[int],
        wavefront: bool,
    ) -> List[Item]:
        """Pop the next expansion slice off the work pool, applying the
        length/capacity guards exactly as the seed stack loop did.
        Wavefront mode drains FIFO up to the slice bounds (many
        patterns, one batched call); pattern mode pops LIFO one at a
        time (the seed's per-pattern dispatch, kept as the benchmark
        baseline)."""
        items: List[Item] = []
        rows = 0
        while pending:
            pattern, enc = (
                pending.popleft() if wavefront else pending.pop()
            )
            if max_len is not None and pattern_length(pattern) >= max_len:
                continue
            if len(pattern) >= self.ni:
                continue  # capacity guard (configurable)
            items.append((pattern, enc))
            rows += len(enc[0])
            if (
                not wavefront
                or len(items) >= self.wave_patterns
                or rows >= self.wave_rows
            ):
                break
        return items

    def _mine(
        self,
        min_support: int,
        max_len: Optional[int],
        rs: bool,
        checkpoint_path: Optional[str] = None,
        checkpoint_every: int = 50,
        resume: bool = False,
    ) -> MiningResult:
        from .checkpoint import load_state, save_state

        res = MiningResult()
        pending: "deque[Item]" = deque([self._root_item()])
        if resume and checkpoint_path:
            patterns, stack, meta = load_state(checkpoint_path)
            res.patterns.update(patterns)
            res.n_enumerated = meta.get("n_enumerated", len(patterns))
            pending = deque(
                (p, encode_embeddings(embs, self.ni, self.nv))
                for p, embs in stack
            )
        # canonical dedup is baseline-only (rs children are unique by
        # the membership test); skip their embedding rebuilds too
        want = (
            None if rs else (lambda child: child not in res.patterns)
        )
        wavefront = self.dispatch == "wavefront"
        expansions_since_ckpt = 0
        with trace.root_or_span("mining.mine", rs=rs,
                                min_support=min_support):
            while pending:
                items = self._take_slice(pending, max_len, wavefront)
                if not items:
                    break  # guards drained the pool
                res.n_extension_scans += len(items)
                self._h_wave.observe(len(items))
                with trace.span("mining.wavefront",
                                patterns=len(items)):
                    for kids in self.expand_children_batch(
                        items, min_support, rs=rs, want_embs=want
                    ):
                        for child, gids, child_enc in kids:
                            if not rs and child in res.patterns:
                                continue
                            res.patterns[child] = len(gids)
                            res.n_enumerated += 1
                            pending.append((child, child_enc))
                expansions_since_ckpt += len(items)
                if (
                    checkpoint_path
                    and expansions_since_ckpt >= checkpoint_every
                ):
                    with trace.span("mining.checkpoint"):
                        save_state(
                            checkpoint_path, res.patterns,
                            [(p, decode_embeddings(enc))
                             for p, enc in pending],
                            meta={"min_support": min_support, "rs": rs,
                                  "n_enumerated": res.n_enumerated},
                        )
                    expansions_since_ckpt = 0
        if checkpoint_path:
            save_state(
                checkpoint_path, res.patterns, [],
                meta={"min_support": min_support, "rs": rs,
                      "n_enumerated": res.n_enumerated, "done": True},
            )
        return res

    def mine_rs(self, min_support: int, max_len: int | None = None,
                **kw) -> MiningResult:
        """GTRACE-RS with device-side extension scans."""
        return self._mine(min_support, max_len, rs=True, **kw)

    def mine_gtrace(self, min_support: int, max_len: int | None = None,
                    **kw) -> MiningResult:
        """Original-GTRACE baseline with device-side extension scans."""
        return self._mine(min_support, max_len, rs=False, **kw)
