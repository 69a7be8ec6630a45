#!/usr/bin/env python
"""Render a phase-attribution table from a saved trace (and gate CI on
trace-file health).

Reads the span traces written by ``repro.obs.trace.save`` - Chrome
``traceEvents`` JSON (``*.json``) or JSONL (one span per line) - and
attributes wall time to buckets by **self time** (a span's duration
minus its nested children), so nesting never double-counts:

* ``host``     - python/numpy bookkeeping (encode, finalize, ring
                 upkeep, routing/merge logic)
* ``dispatch`` - jax launch cost (the async call returning)
* ``device``   - spans a caller marks ``cat="device"``; the program
                 records none (its launch spans end when the async call
                 returns, and device time is read from the JAX
                 profiler's device planes, where its spans also land)
* ``cache``    - fingerprint + L1/L2 cache resolution
* root spans (``cat="wall"``) define the denominator; their own self
  time is reported as *(uninstrumented)* - the honesty line: gaps the
  instrumentation does not explain.

This is the tool that answers "where did the H4 qps go": run
``benchmarks/bench_cluster.py --smoke --trace /tmp/t.json`` and the
table splits a routed drain into e.g. per-shard dispatch overhead vs
device time vs cache hits vs merge cost.

Examples::

    python benchmarks/bench_cluster.py --smoke --trace /tmp/t.json
    python scripts/trace_report.py /tmp/t.json
    python scripts/trace_report.py /tmp/t.json --top 15 --json
    python scripts/trace_report.py /tmp/t.json --check --min-coverage 0.9
    python scripts/trace_report.py /tmp/t.json \
        --metrics BENCH_cluster_smoke.json --slo scripts/slo_rules.json

``--check`` is the CI tier-6 gate: it validates the trace schema
(every span well-formed, categories known, at least one root span) and
fails when attribution coverage - the non-uninstrumented share of wall
time - drops below ``--min-coverage`` (default 0.9).  Exit code 0 =
healthy trace.

``--metrics PATH`` reads a metrics snapshot (a BENCH artifact with a
``metrics`` block, or a flat ``{name: value}`` JSON) and renders the
latency percentile block from the bucket-histogram keys
(``*_seconds.p50/.p95/.p99``).  ``--slo RULES.json`` additionally
evaluates the declarative SLO rules (``repro.obs.slo``) against that
snapshot and exits nonzero on any breach - the tier-6 gate reads SLOs,
not just coverage.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

BUCKETS = ("device", "dispatch", "cache", "host")
CATEGORIES = BUCKETS + ("wall",)


class TraceError(Exception):
    pass


def load_events(path: str) -> List[Dict[str, Any]]:
    """Load spans from Chrome-trace JSON or JSONL into the internal
    {name, cat, ts, dur, trace, args} form (times in microseconds)."""
    with open(path) as f:
        text = f.read()
    text = text.strip()
    if not text:
        raise TraceError(f"{path}: empty trace file")
    # format sniff: a JSONL line is itself a JSON object, so "starts
    # with {" cannot distinguish the formats - parse the first line
    # and look for the Chrome traceEvents envelope
    first_line = text.splitlines()[0]
    try:
        head = json.loads(first_line)
        is_chrome = isinstance(head, dict) and "traceEvents" in head
    except json.JSONDecodeError:
        is_chrome = True  # pretty-printed (multi-line) Chrome JSON
    if is_chrome:
        doc = json.loads(text)
        if "traceEvents" not in doc:
            raise TraceError(f"{path}: no traceEvents key")
        events = []
        for ev in doc["traceEvents"]:
            if ev.get("ph") != "X":
                continue
            args = dict(ev.get("args", {}))
            trace = args.pop("trace", None)
            events.append({
                "name": ev.get("name"), "cat": ev.get("cat"),
                "ts": ev.get("ts"), "dur": ev.get("dur"),
                "trace": trace, "args": args,
            })
        return events
    events = []
    for i, line in enumerate(text.splitlines()):
        try:
            events.append(json.loads(line))
        except json.JSONDecodeError as e:
            raise TraceError(f"{path}:{i + 1}: bad JSONL span: {e}")
    return events


def validate(events: List[Dict[str, Any]]) -> List[str]:
    """Schema check: every span well-formed, categories known, at
    least one root.  Returns a list of problems (empty = healthy)."""
    problems = []
    n_wall = 0
    for i, ev in enumerate(events):
        if not isinstance(ev.get("name"), str) or not ev["name"]:
            problems.append(f"span {i}: missing/empty name")
            continue
        cat = ev.get("cat")
        if cat not in CATEGORIES:
            problems.append(
                f"span {i} ({ev['name']}): unknown cat {cat!r}")
        for key in ("ts", "dur"):
            v = ev.get(key)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v < 0:
                problems.append(
                    f"span {i} ({ev['name']}): bad {key}={v!r}")
        tr = ev.get("trace")
        if tr is not None and not isinstance(tr, int):
            problems.append(
                f"span {i} ({ev['name']}): bad trace id {tr!r}")
        if cat == "wall":
            n_wall += 1
    if not events:
        problems.append("trace contains no spans")
    elif n_wall == 0:
        problems.append(
            "no root (cat='wall') span - nothing defines wall time")
    return problems


def attribute(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Self-time attribution: one interval-nesting sweep.  Spans from
    one process/timeline are properly nested, so a (ts-sorted) stack
    walk assigns each span's duration minus its children to its own
    category."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i]["ts"], -events[i]["dur"]))
    child_dur = [0.0] * len(events)
    depth = [0] * len(events)
    stack: List[int] = []  # indices of open spans
    eps = 1e-6  # µs; absorbs float noise at shared boundaries
    for i in order:
        ev = events[i]
        while stack and (events[stack[-1]]["ts"]
                         + events[stack[-1]]["dur"]) <= ev["ts"] + eps:
            stack.pop()
        if stack:
            child_dur[stack[-1]] += ev["dur"]
            depth[i] = len(stack)
        stack.append(i)

    wall = sum(ev["dur"] for i, ev in enumerate(events)
               if depth[i] == 0)
    buckets = {b: 0.0 for b in BUCKETS}
    uninstrumented = 0.0
    by_name: Dict[str, Dict[str, float]] = {}
    for i, ev in enumerate(events):
        self_t = max(0.0, ev["dur"] - child_dur[i])
        if ev["cat"] == "wall":
            uninstrumented += self_t
        else:
            buckets[ev["cat"]] = buckets.get(ev["cat"], 0.0) + self_t
        agg = by_name.setdefault(
            ev["name"], {"self": 0.0, "dur": 0.0, "count": 0,
                         "cat": ev["cat"]})
        agg["self"] += self_t
        agg["dur"] += ev["dur"]
        agg["count"] += 1

    subsystems: Dict[str, Dict[str, float]] = {}
    for i, ev in enumerate(events):
        if ev["cat"] == "wall":
            continue
        sub = ev["name"].split(".", 1)[0]
        row = subsystems.setdefault(sub, {b: 0.0 for b in BUCKETS})
        row[ev["cat"]] += max(0.0, ev["dur"] - child_dur[i])

    n_traces = len({ev["trace"] for ev in events
                    if ev.get("trace") is not None})
    coverage = 1.0 - (uninstrumented / wall) if wall > 0 else 0.0
    return {
        "wall_us": wall,
        "buckets_us": buckets,
        "uninstrumented_us": uninstrumented,
        "coverage": coverage,
        "by_name": by_name,
        "subsystems": subsystems,
        "n_spans": len(events),
        "n_traces": n_traces,
    }


def _pct(x: float, wall: float) -> str:
    return f"{100.0 * x / wall:5.1f}%" if wall > 0 else "    -"


def render(report: Dict[str, Any], top: int = 12) -> str:
    wall = report["wall_us"]
    lines = []
    lines.append(f"trace: {report['n_spans']} spans, "
                 f"{report['n_traces']} traces, "
                 f"wall {wall / 1e6:.4f}s")
    lines.append("")
    lines.append("phase attribution (self time per bucket)")
    lines.append(f"  {'bucket':<16} {'seconds':>10}  share")
    for b in BUCKETS:
        v = report["buckets_us"][b]
        lines.append(f"  {b:<16} {v / 1e6:>10.4f}  {_pct(v, wall)}")
    u = report["uninstrumented_us"]
    lines.append(f"  {'(uninstrumented)':<16} {u / 1e6:>10.4f}  "
                 f"{_pct(u, wall)}")
    lines.append(f"  {'wall':<16} {wall / 1e6:>10.4f}  100.0%")
    lines.append(f"  coverage: {100.0 * report['coverage']:.1f}% of "
                 f"wall time attributed")
    if report["subsystems"]:
        lines.append("")
        lines.append("per subsystem (self-time share of wall)")
        lines.append("  " + f"{'subsystem':<12}" + "".join(
            f"{b:>10}" for b in BUCKETS))
        for sub in sorted(report["subsystems"],
                          key=lambda s: -sum(
                              report["subsystems"][s].values())):
            row = report["subsystems"][sub]
            lines.append("  " + f"{sub:<12}" + "".join(
                _pct(row[b], wall).rjust(10) for b in BUCKETS))
    lines.append("")
    lines.append(f"top spans by self time")
    lines.append(f"  {'span':<34} {'cat':<9} {'count':>7} "
                 f"{'self_s':>9}  share")
    ranked = sorted(report["by_name"].items(),
                    key=lambda kv: -kv[1]["self"])[:top]
    for name, agg in ranked:
        lines.append(
            f"  {name:<34} {agg['cat']:<9} {agg['count']:>7} "
            f"{agg['self'] / 1e6:>9.4f}  {_pct(agg['self'], wall)}"
        )
    return "\n".join(lines)


def load_metrics(path: str) -> Dict[str, float]:
    """Flat metrics snapshot from a BENCH artifact (its ``metrics``
    block) or a flat ``{name: value}`` JSON dict."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise TraceError(f"{path}: metrics file is not a JSON object")
    if "metrics" in doc and isinstance(doc["metrics"], dict):
        doc = doc["metrics"]
    return {k: v for k, v in doc.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def render_percentiles(snap: Dict[str, float]) -> str:
    """The latency percentile block: one row per bucket histogram
    that exported quantiles (``<base>.p50/.p95/.p99`` snapshot keys)."""
    bases = sorted({k[: -len(".p50")] for k in snap if k.endswith(".p50")})
    if not bases:
        return "latency percentiles: (no bucket histograms in snapshot)"
    lines = ["latency percentiles (bucket-histogram upper bounds)"]
    lines.append(f"  {'histogram':<40} {'count':>8} {'p50':>10} "
                 f"{'p95':>10} {'p99':>10} {'max':>10}")
    for base in bases:
        def col(suffix):
            v = snap.get(f"{base}.{suffix}")
            if v is None:
                return "-".rjust(10)
            return f"{v * 1e3:>9.3f}m" if suffix != "count" \
                else f"{int(v):>8}"
        lines.append(f"  {base:<40} {col('count')} {col('p50')} "
                     f"{col('p95')} {col('p99')} {col('max')}")
    return "\n".join(lines)


def check_slo(rules_path: str, snap: Dict[str, float]) -> int:
    """Evaluate declarative SLO rules against the snapshot; prints a
    verdict per rule set and returns the breach count."""
    from repro.obs.slo import evaluate, load_rules
    rules = load_rules(rules_path)
    breaches = evaluate(rules, snap)
    for b in breaches:
        print(f"[trace_report] {b}")
    if breaches:
        print(f"[trace_report] SLO FAIL: {len(breaches)} of "
              f"{len(rules)} rule(s) breached")
    else:
        print(f"[trace_report] SLO OK: {len(rules)} rule(s) within "
              "bounds")
    return len(breaches)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("trace", help="trace file (.json Chrome trace or "
                                  "JSONL from repro.obs.trace.save)")
    ap.add_argument("--check", action="store_true",
                    help="CI gate: validate the span schema and fail "
                         "below --min-coverage attribution")
    ap.add_argument("--min-coverage", type=float, default=0.9,
                    help="minimum attributed share of wall time for "
                         "--check (default 0.9)")
    ap.add_argument("--top", type=int, default=12,
                    help="rows in the top-spans table")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable summary instead "
                         "of the table")
    ap.add_argument("--metrics", metavar="PATH",
                    help="metrics snapshot (BENCH artifact or flat "
                         "JSON): renders the latency percentile block")
    ap.add_argument("--slo", metavar="RULES.json",
                    help="evaluate SLO rules against --metrics; exit "
                         "nonzero on any breach")
    args = ap.parse_args(argv)
    if args.slo and not args.metrics:
        ap.error("--slo requires --metrics")

    try:
        events = load_events(args.trace)
    except (OSError, json.JSONDecodeError, TraceError) as e:
        print(f"[trace_report] FAIL: {e}")
        return 1
    problems = validate(events)
    if problems:
        for p in problems:
            print(f"[trace_report] malformed span: {p}")
        if args.check:
            print(f"[trace_report] FAIL: {len(problems)} schema "
                  "problem(s)")
            return 1
    report = attribute(events)
    if args.json:
        out = dict(report)
        out["by_name"] = {k: v for k, v in sorted(
            report["by_name"].items())}
        print(json.dumps(out, indent=2))
    else:
        print(render(report, top=args.top))
    if args.check:
        if report["coverage"] < args.min_coverage:
            print(f"[trace_report] FAIL: coverage "
                  f"{report['coverage']:.3f} < "
                  f"{args.min_coverage:.3f} - the instrumentation "
                  "does not explain enough of the wall time")
            return 1
        print(f"[trace_report] check OK: {report['n_spans']} spans, "
              f"coverage {report['coverage']:.3f} >= "
              f"{args.min_coverage:.3f}")
    if args.metrics:
        try:
            snap = load_metrics(args.metrics)
        except (OSError, json.JSONDecodeError, TraceError) as e:
            print(f"[trace_report] FAIL: {e}")
            return 1
        if not args.json:
            print()
            print(render_percentiles(snap))
        if args.slo and check_slo(args.slo, snap):
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
