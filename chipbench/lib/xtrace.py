"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load_dir`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
under a trace directory into plain event lists (``ProfileData`` needs
nothing beyond JAX); ``reduce`` turns them into numbers:

* ``busy_s``: the union of the intervals in which an operation ran on
  the device (the ``XLA Ops`` lines of each ``/device:TPU:n`` plane),
  averaged over the devices that ran anything;
* ``window_s``: the length of the benchmark's ``window`` annotation,
  the traced window (without one: from the first to the last event);
* ``programs``: device seconds per program (the ``XLA Modules`` lines),
  keyed by program name with the ``(id)`` suffix dropped, so that
  ``jit_pair_contains_indexed(123)`` counts as
  ``jit_pair_contains_indexed``;
* ``top_ops``: the device operations that took most time;
* ``idle_gaps``: the seconds in which the device ran nothing, summed by
  what the host was doing: each stretch of idle time is named by the
  innermost of the benchmark's own host spans (``submit``, ``collect``,
  ``mine_rs``...) that holds its midpoint, or ``"(none)"``; the longest
  sums first.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

Event = Tuple[str, int, int]  # name, start ns, duration ns

# the host spans the benchmark's drivers put around calls into a layer
SPANS = ("window", "submit", "poll", "collect", "generate", "mine_rs",
         "observe", "refresh")

_SUFFIX = re.compile(r"\(\d+\)$")


def load_dir(path: str) -> dict:
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return load(files[-1])


def load(path: str) -> dict:
    """``{"ops": {device: [Event]}, "modules": {device: [Event]},
    "host": [Event]}`` from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = defaultdict(list), defaultdict(list), []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] += [(e.name, e.start_ns, e.duration_ns)
                                        for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] += [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events]
    return {"ops": dict(ops), "modules": dict(modules), "host": host}


def _union(events: List[Event]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce(tr: dict, spans=SPANS, n_top: int = 10) -> dict:
    """The trace's numbers (see the module docstring); idle gaps are
    named by host events whose name is in ``spans``."""
    win = [e for e in tr["host"] if e[0] == "window"]
    host = [e for e in tr["host"] if e[0] in spans and e[0] != "window"]
    if win:
        t0, t1 = win[0][1], win[0][1] + win[0][2]
    else:
        ends = [x for evs in tr["ops"].values() for _, s, d in evs
                for x in (s, s + d)]
        ends += [x for _, s, d in host for x in (s, s + d)]
        t0, t1 = (min(ends), max(ends)) if ends else (0, 0)
    ops = {}
    for dev, evs in tr["ops"].items():
        evs = [(n, max(s, t0), min(s + d, t1) - max(s, t0))
               for n, s, d in evs if s < t1 and s + d > t0]
        if evs:
            ops[dev] = evs
    if not ops:
        return {"busy_s": 0.0, "window_s": (t1 - t0) * 1e-9,
                "programs": {}, "top_ops": [], "idle_gaps": []}
    busy = 0.0
    gaps = []
    for evs in ops.values():
        iv = _union(evs)
        busy += sum(e - s for s, e in iv)
        prev = t0
        for s, e in iv:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if t1 > prev:
            gaps.append((prev, t1))
    busy /= len(ops)

    per_op: Dict[str, float] = defaultdict(float)
    for evs in ops.values():
        for name, _, d in evs:
            per_op[name] += d * 1e-9
    programs: Dict[str, float] = defaultdict(float)
    for evs in tr["modules"].values():
        for name, s, d in evs:
            d = min(s + d, t1) - max(s, t0)
            if d > 0:
                programs[_SUFFIX.sub("", name)] += d * 1e-9 / len(ops)

    host.sort(key=lambda h: h[1])
    starts = [h[1] for h in host]
    longest = max((h[2] for h in host), default=0)

    def label(s, e):
        mid = (s + e) / 2
        best = None
        k = bisect.bisect_right(starts, mid) - 1
        while k >= 0 and host[k][1] >= mid - longest:
            h = host[k]
            if mid <= h[1] + h[2] and (best is None or h[2] < best[2]):
                best = h
            k -= 1
        return best[0] if best else "(none)"

    idle: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        idle[label(s, e)] += (e - s) * 1e-9 / len(ops)
    return {
        "busy_s": busy * 1e-9,
        "window_s": (t1 - t0) * 1e-9,
        "programs": dict(programs),
        "top_ops": [[k, v] for k, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:n_top]],
        "idle_gaps": [[k, v] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:n_top]],
    }
