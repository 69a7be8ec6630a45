"""Open-loop serving through ``ServingCluster``'s admission pipeline.

Set-up: the configuration's bank (reference patterns, compiled by the
program), the run's arrival times and fresh request sequences from the
seed, and one host behind the router with the configuration's
``flush_batch`` and ``max_wait``.

The router decides when to launch a batch (``flush_batch`` arrivals
queued, or the head of the queue ``max_wait`` old).  It reads its clock
from the driver, which hands it the scheduled time of the event being
handled: an arrival's due time, or the due time of a deadline.  So which
requests share a batch follows from the schedule alone, and set-up can
replay the window's schedule as fast as it runs to compile every shape
the window will use.  The replay's rows then leave the router's caches
and the fingerprint memo, so the window starts with nothing cached.

The window: each request is submitted at its due time (or as soon after
as the loop is free), deadlines are pumped with ``poll``, and whenever
the loop would otherwise wait for the next event it collects every
launched batch.  A request's latency runs on the host clock from its due
time to the return of the ``collect`` that answered it; one that is not
answered exactly within 60 s of the window's end counts as infinite.

Check: every answer must be exact; a sample of them drawn from the seed
is compared row for row, and top-k for top-k, with the reference.
"""
from __future__ import annotations

import contextlib
import math
import time

import numpy as np

from chipbench.lib import program, reference, traffic

GRACE_S = 60.0
TOPK = 10  # the router's default top-k, which the cluster is built with


class VirtualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _flushes(cluster) -> int:
    s = cluster.router.stats
    return s["flush_batch"] + s["flush_deadline"] + s["flush_force"]


def _queued(cluster) -> int:
    """Misses the router has put in its queue so far (a shed miss is
    answered at once and never queued)."""
    s = cluster.router.stats
    return s["misses"] - s["shed_prescreen"]


def drive(cluster, vclock, times, reqs, max_wait, *, realtime, offset=0.0,
          seconds=math.inf, span=None):
    """Serve ``reqs[i]`` due at ``times[i]``.  With ``realtime`` the
    loop keeps to the schedule on the host clock; without, it runs the
    same schedule as fast as it can.  Returns per request the time the
    batch holding it was launched, the time it was answered (both
    relative to the start), its result, and how late each arrival was
    submitted."""
    n = len(times)
    launched_at = np.full(n, np.nan)
    done = np.full(n, np.nan)
    results = [None] * n
    late = []
    tickets = {}
    queued, riders, launched = [], [], []
    i, nflush, nqueued = 0, _flushes(cluster), _queued(cluster)
    span = span or (lambda name: contextlib.nullcontext())
    t0 = time.perf_counter()

    def now():
        return time.perf_counter() - t0

    while i < n or queued or launched:
        t_arr = times[i] if i < n else math.inf
        t_dl = times[queued[0]] + max_wait if queued else math.inf
        t_ev = min(t_arr, t_dl)
        if launched and (not realtime or t_ev == math.inf
                         or now() < t_ev):
            with span("collect"):
                for idx, tk in launched:
                    results[idx] = cluster.collect(tk)[0][0]
                    done[idx] = now()
            launched = []
            continue
        if realtime:
            if now() > seconds + GRACE_S:
                break
            wait = t_ev - now()
            if wait > 0:
                time.sleep(wait)
        # a deadline is handed over a nanosecond late, so that the
        # router's age test sees it reached despite float rounding
        vclock.t = offset + t_ev + (0.0 if t_arr <= t_dl else 1e-9)
        if t_arr <= t_dl:
            if realtime:
                late.append(max(0.0, now() - t_arr))
            with span("submit"):
                tickets[i] = cluster.submit({0: [reqs[i]]})
            q = _queued(cluster)
            if q != nqueued:
                queued.append(i)
            elif queued:
                riders.append(i)   # shares a join that is still queued
            else:
                launched_at[i] = now()
                launched.append((i, tickets[i]))
            nqueued = q
            i += 1
        else:
            with span("poll"):
                cluster.poll()
        f = _flushes(cluster)
        if t_arr > t_dl and f == nflush:
            raise RuntimeError("the router did not launch at a deadline")
        if f != nflush:
            nflush = f
            t = now()
            for idx in queued + riders:
                launched_at[idx] = t
                launched.append((idx, tickets[idx]))
            queued, riders = [], []
    return launched_at, done, results, late


def reference_answer(code_rows, bank, s, k):
    """The reference row of sequence ``s`` over the bank rows, and its
    top-k by support, ties by row."""
    idx = reference._index(s)
    row = np.array([c is not None and reference.contains(
        reference.from_code(c), s, idx) for c in code_rows], bool)
    sup = np.array([bank.get(c, 0) if c is not None else 0
                    for c in code_rows])
    hit = np.nonzero(row)[0]
    order = sorted(hit, key=lambda r: (-sup[r], r))[:k]
    return row, [(int(r), int(sup[r])) for r in order]


def run(ctx) -> dict:
    from repro.serving.bank import sequence_fingerprint
    from repro.serving.cluster import ServingCluster

    cfg, mix = ctx.config, ctx.mix
    router = cfg["router"]
    with ctx.phase("bank"):
        bank = program.load_bank(cfg)
        pbank, code_rows = program.compile_bank(bank)
    with ctx.phase("generate"):
        times = traffic.arrival_times(mix, ctx.seconds, ctx.seed)
        plain_reqs = traffic.requests(cfg, mix, len(times), ctx.seed)
        reqs = [program.seq(s) for s in plain_reqs]
    vclock = VirtualClock()
    cluster = ServingCluster(
        pbank, 1, topk=TOPK, clock=vclock, flush_batch=int(router["flush_batch"]),
        max_wait=float(router["max_wait_s"]),
        # the control: the program's own prescreen-only tier, inexact
        shed_depth=0 if ctx.control == "approx" else None)
    wait = float(router["max_wait_s"])
    with ctx.phase("replay"):
        drive(cluster, vclock, times, reqs, wait, realtime=False)
    cluster.router.clear_caches()
    sequence_fingerprint.cache_clear()
    before = dict(cluster.stats())
    ctx.setup_done()

    rec = {"kind": "serve", "seconds": ctx.seconds}
    with ctx.window(rec):
        launched_at, done, results, late = drive(
            cluster, vclock, times, reqs, wait, realtime=True,
            offset=ctx.seconds + 1.0, seconds=ctx.seconds, span=ctx.span)
    after = dict(cluster.stats())
    rec["counters"] = {k: after.get(k, 0) - before.get(k, 0) for k in after}

    n = len(times)
    exact = np.array([r is not None and bool(r.exact) for r in results])
    answered = np.array([r is not None for r in results])
    lat = np.where(exact & ~np.isnan(done), done - times, np.inf)
    rec["latency_s"] = lat.tolist()
    rec["queue_wait_s"] = np.where(np.isnan(launched_at), np.inf,
                                   launched_at - times).tolist()
    rec["exact_answers"] = int(exact.sum())
    # the window runs until the last request due in it is answered
    rec["served_s"] = float(max(ctx.seconds, np.nanmax(done, initial=0.0)))
    rec["attempted"] = n
    rec["failed"] = int((~exact).sum())
    lt = sorted(late) or [0.0]
    quarters = [traffic.percentile(q, 50) for q in np.array_split(lat, 4)]
    rec["notes"] = [
        f"[serve] {n} requests at {mix['rate_per_s']}/s over "
        f"{ctx.seconds}s; {int(answered.sum())} answered, "
        f"{int(exact.sum())} exact; counters {rec['counters']}",
        f"[generator] submit lateness p50 {traffic.percentile(lt, 50):.6f}s "
        f"p95 {traffic.percentile(lt, 95):.6f}s max {lt[-1]:.6f}s",
        f"[backlog] median latency by quarter of the arrivals "
        f"{[round(q, 6) for q in quarters]}s; p95 "
        f"{traffic.percentile(lat, 95):.6f}s",
    ]

    # ---- check against the reference, once the window has closed
    rows_off = sum(c is None for c in code_rows) + len(
        set(bank) - set(c for c in code_rows if c is not None))
    rng = np.random.default_rng(traffic.derive(ctx.seed, "check"))
    cand = np.nonzero(answered)[0]
    k = min(int(cfg["check"]["sample"]), len(cand))
    sample = np.sort(rng.choice(cand, size=k, replace=False)) if k else []
    wrong_cells = wrong_topk = 0
    t0 = time.perf_counter()
    for i in sample:
        r = results[i]
        row, top = reference_answer(code_rows, bank, plain_reqs[i], TOPK)
        wrong_cells += int((np.asarray(r.contained, bool) != row).sum())
        wrong_topk += int([tuple(map(int, t)) for t in r.topk] != top)
    rec["notes"].append(
        f"[check] {k} sampled answers against the reference in "
        f"{time.perf_counter() - t0:.3f}s")
    rec["checks"] = {
        "bank_rows_off": {"value": rows_off, "limit": 0},
        "unanswered": {"value": int((~answered).sum()), "limit": 0},
        "inexact": {"value": int((answered & ~exact).sum()), "limit": 0},
        "wrong_cells": {"value": wrong_cells, "limit": 0},
        "wrong_topk": {"value": wrong_topk, "limit": 0},
    }
    return rec
