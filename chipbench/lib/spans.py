"""Device-idle time named by the program's own spans.

While a profiler trace is taken, the program's tracer
(``repro.obs.trace``) opens a profiler annotation for each of its spans,
so they sit on the trace's host plane beside the benchmark's own spans
(``submit``, ``collect``, ``mine_rs``...) and on the device's clock.
``reduce`` takes a trace as ``xtrace.load`` reads it and returns:

* ``idle_by_path``: the seconds in which the device ran nothing within
  the benchmark's ``window`` annotation, split exactly at span edges.
  Each piece of idle time goes to the full stack of spans covering it,
  outermost first, joined by ``>``
  (``collect>cluster.collect>cluster.fence>serving.finalize_rows``), or
  to ``"(none)"`` where no span covers it.  Over several devices each
  device's idle time counts ``1 / devices``, so the values sum to
  ``window_s - busy_s`` of ``xtrace.reduce``.
* ``span_count``: the number of span events per name that start in the
  window.

A span is one of the benchmark's (``xtrace.SPANS`` but ``window``) or a
program span: a host event whose name starts with one of ``PROGRAM``.
The drivers and the program run spans on one thread, so spans nest.

``idle_ms_per`` is what a per-layer reader needs: the idle milliseconds
under a subtree of spans over a denominator, or ``None`` where the trace
holds none of the subtree's spans (a program that lacks them).
``summary`` gives the shares of the ``[spans]`` line.
"""
from __future__ import annotations

from collections import Counter, defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

from . import xtrace

PROGRAM = ("mining.", "serving.", "cluster.", "streaming.")
# program spans that only wrap others: idle time in their self time is
# not named by a phase
WRAPPERS = ("mining.mine", "mining.wavefront", "cluster.submit",
            "cluster.collect")
NONE = "(none)"


def is_program(name: str) -> bool:
    return name.startswith(PROGRAM)


def _segments(host: List[Tuple[str, int, int]], t0: int,
              t1: int) -> List[Tuple[int, int, str]]:
    """``[(start, end, path)]`` tiling ``[t0, t1]``: the stack of spans
    (``(name, start, end)``) covering each stretch, outermost first."""
    pts = sorted({t0, t1, *(s for _, s, _ in host),
                  *(e for _, _, e in host)})
    by_start = sorted(range(len(host)), key=lambda i: host[i][1])
    by_end = sorted(range(len(host)), key=lambda i: host[i][2])
    active: Dict[int, Tuple[str, int, int]] = {}
    si = ei = 0
    out: List[Tuple[int, int, str]] = []
    for a, b in zip(pts, pts[1:]):
        while ei < len(by_end) and host[by_end[ei]][2] <= a:
            active.pop(by_end[ei], None)
            ei += 1
        while si < len(by_start) and host[by_start[si]][1] <= a:
            i = by_start[si]
            si += 1
            if host[i][2] > a:
                active[i] = host[i]
        path = ">".join(h[0] for h in sorted(
            active.values(), key=lambda h: (h[1], -h[2]))) or NONE
        if out and out[-1][2] == path:
            out[-1] = (out[-1][0], b, path)
        else:
            out.append((a, b, path))
    return out


def _gaps(evs: List[xtrace.Event], t0: int,
          t1: int) -> List[Tuple[int, int]]:
    """The stretches of ``[t0, t1]`` in which none of ``evs`` (cut to
    ``[t0, t1]``) ran."""
    out, prev = [], t0
    for s, e in xtrace._union(evs):
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    if t1 > prev:
        out.append((prev, t1))
    return out


def reduce(tr: dict, spans: Iterable[str] = xtrace.SPANS) -> dict:
    """``idle_by_path`` and ``span_count`` (see the module docstring)."""
    bench = set(spans) - {"window"}
    win = [e for e in tr["host"] if e[0] == "window"]
    named = [e for e in tr["host"] if e[0] in bench or is_program(e[0])]
    if win:
        t0, t1 = win[0][1], win[0][1] + win[0][2]
    else:
        ends = [x for evs in tr["ops"].values() for _, s, d in evs
                for x in (s, s + d)]
        ends += [x for _, s, d in named for x in (s, s + d)]
        t0, t1 = (min(ends), max(ends)) if ends else (0, 0)
    count = Counter(n for n, s, _ in named if t0 <= s < t1)
    host = [(n, max(s, t0), min(s + d, t1)) for n, s, d in named
            if s < t1 and s + d > t0]
    segs = _segments(host, t0, t1)
    devices = [cut for cut in (
        [(n, max(s, t0), min(s + d, t1) - max(s, t0))
         for n, s, d in evs if s < t1 and s + d > t0]
        for evs in tr["ops"].values()) if cut]
    idle: Dict[str, float] = defaultdict(float)
    for evs in devices:
        k = 0
        for gs, ge in _gaps(evs, t0, t1):
            while segs[k][1] <= gs:
                k += 1
            j = k
            while j < len(segs) and segs[j][0] < ge:
                a, b, path = segs[j]
                over = min(ge, b) - max(gs, a)
                if over > 0:
                    idle[path] += over * 1e-9 / len(devices)
                j += 1
    return {"idle_by_path": dict(idle), "span_count": dict(count)}


def _names(path: str) -> List[str]:
    return [] if path == NONE else path.split(">")


def idle_under(idle_by_path: Dict[str, float], include: Iterable[str],
               exclude: Iterable[str] = ()) -> float:
    """Idle seconds on paths that hold any span of ``include`` and none
    of ``exclude``."""
    include, exclude = set(include), set(exclude)
    total = 0.0
    for path, v in idle_by_path.items():
        names = set(_names(path))
        if names & include and not names & exclude:
            total += v
    return total


def idle_ms_per(rec: dict, include: Iterable[str], per,
                exclude: Iterable[str] = ()) -> Optional[float]:
    """Idle milliseconds under ``include`` (less ``exclude``) over
    ``per``; ``None`` without a trace of a device, without a
    denominator, or where the trace holds none of the spans in
    ``include``."""
    include = tuple(include)
    t = rec.get("trace") or {}
    paths, count = t.get("idle_by_path"), t.get("span_count") or {}
    if not paths or not per or not any(count.get(n) for n in include):
        return None
    return 1000.0 * idle_under(paths, include, exclude) / per


def _innermost_program(path: str) -> Optional[str]:
    names = [n for n in _names(path) if is_program(n)]
    return names[-1] if names else None


def summary(idle_by_path: Dict[str, float],
            scope: Iterable[str] = ("submit", "poll", "collect",
                                    "mine_rs")) -> dict:
    """Shares of the window's idle time: under a program span below the
    wrappers (``phase``), in a wrapper's self time (``wrapper``) and
    under no program span (``outside``); and ``phase_in_scope``, the
    share below the wrappers of the idle time inside the benchmark's
    calls into the program (spans ``scope``)."""
    scope = set(scope)
    total = sum(idle_by_path.values())
    parts = {"phase": 0.0, "wrapper": 0.0, "outside": 0.0}
    in_scope = phase_in_scope = 0.0
    for path, v in idle_by_path.items():
        inner = _innermost_program(path)
        kind = ("outside" if inner is None else
                "wrapper" if inner in WRAPPERS else "phase")
        parts[kind] += v
        if scope & set(_names(path)):
            in_scope += v
            phase_in_scope += v if kind == "phase" else 0.0
    share = {k: 100.0 * v / total if total else 0.0
             for k, v in parts.items()}
    share["phase_in_scope"] = (100.0 * phase_in_scope / in_scope
                               if in_scope else 0.0)
    share["idle_s"] = total
    return share


def line(red: dict, window_s: float, per_job: Optional[dict] = None) -> str:
    """The ``[spans]`` line of a traced run."""
    s = summary(red["idle_by_path"])
    events = sum(n for name, n in red["span_count"].items()
                 if is_program(name))
    out = (f"[spans] {s['idle_s']:.6f}s idle: {s['phase']:.3f}% under a "
           f"program phase, {s['wrapper']:.3f}% in wrapper self time "
           f"({', '.join(WRAPPERS)}), {s['outside']:.3f}% outside any "
           f"program span; inside the benchmark's calls "
           f"{s['phase_in_scope']:.3f}% under a phase; {events} program "
           f"span events ({events / window_s if window_s else 0.0:.1f}/s)")
    if per_job:
        out += "; per job " + ", ".join(f"{k} {v:.3f}"
                                        for k, v in per_job.items())
    return out
