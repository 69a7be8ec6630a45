"""Runs one cell of ``BENCHMARK.json`` once and prints its result line.

    python3 chipbench/run.py --workload table3.serve --seed 7 --seconds 30 --trace 0

Everything is found by name: the cell's entry in ``BENCHMARK.json``
names a configuration (``chipbench/configs/<config>.json``) and a
traffic mix (``chipbench/traffic/<traffic>.json``); the mix's ``kind``
names the driver (``chipbench/kinds/<kind>.py``); each metric of the
cell is read by ``chipbench/metrics/<metric>.py``.  A driver builds the
cell's inputs from the seed, warms up, runs the measured window and
checks what the window produced against the plain reference
(``lib/reference.py``).  It returns a record, a dict, that the metric
readers read; a reader that finds nothing returns ``None`` and its
metric is left out.

The run needs a TPU: with none, or fewer chips than the cell asks for,
it exits 3 and prints no result.  JAX's persistent compilation cache is
kept at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
CACHE_DIR = ROOT / ".jax_cache"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(Exception):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(name: str, bench: dict | None = None) -> dict:
    """The cell ``name`` with its configuration, mix and metrics."""
    bench = load_json(ROOT / "BENCHMARK.json") if bench is None else bench
    w = next((w for w in bench["workloads"] if w["name"] == name), None)
    if w is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")

    def mine(ms):
        return [m for m in ms if name in m.get("workloads", [name])]

    return {
        "workload": w,
        "config": load_json(BENCH / "configs" / f"{w['config']}.json"),
        "mix": load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        "end_to_end": mine(bench["end_to_end"]),
        "per_layer": mine(bench["per_layer"]),
    }


class CompileClock:
    """Backend compiles (a persistent-cache load counts as one), their
    seconds, cache hits and jaxpr traces, from JAX's monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.seconds += duration
            self.compiles += 1
        elif event == TRACE_EVENT:
            self.traces += 1

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def snap(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.seconds,
                "cache_hits": self.cache_hits, "traces": self.traces}


def dir_bytes(path: Path) -> int:
    return sum(f.stat().st_size for f in path.rglob("*") if f.is_file()) \
        if path.exists() else 0


class Context:
    """What a driver gets: the cell, the run's arguments, spans for the
    profiler, and the set-up and window clocks."""

    def __init__(self, args, spec, t_start, jax=None, devices=None):
        self.args = args
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.control = args.control
        self.spec = spec
        self.config = spec["config"]
        self.mix = spec["mix"]
        self.t_start = t_start
        self.jax = jax
        self.devices = devices
        self.clock = CompileClock(jax) if jax is not None else None
        self.setup_s = None
        self.marks = {}
        self.phases = [("start", time.perf_counter() - t_start, 0)]

    def span(self, name: str):
        """A host span in the profiler's trace (no cost when no trace
        is being taken)."""
        if self.jax is None:
            return contextlib.nullcontext()
        return self.jax.profiler.TraceAnnotation(name)

    def snap(self) -> dict:
        return self.clock.snap() if self.clock else {}

    @contextlib.contextmanager
    def phase(self, name: str):
        """A named part of set-up: a span, and its seconds and
        compiles in the ``[setup]`` line."""
        t0, c0 = time.perf_counter(), self.snap()
        with self.span(name):
            yield
        c1 = self.snap()
        self.phases.append((name, time.perf_counter() - t0,
                            c1.get("compiles", 0) - c0.get("compiles", 0)))

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start
        self.marks["setup"] = self.snap()

    @contextlib.contextmanager
    def window(self, rec: dict):
        """The measured window: compile counts around it, and with
        ``--trace 1`` the profiler's trace of it, reduced into
        ``rec["trace"]``."""
        tmp = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if self.trace else None
        if tmp:
            self.jax.profiler.start_trace(tmp)
        before = self.snap()
        t0 = time.perf_counter()
        try:
            with self.span("window"):
                yield
        finally:
            rec["window_wall_s"] = time.perf_counter() - t0
            # read before the reference check runs: a process's peak
            # never falls again
            rec["memory_peak_bytes"] = memory_peak(self.devices)
            after = self.snap()
            rec["window_compiles"] = {k: after[k] - before[k]
                                      for k in after}
            if tmp:
                self.jax.profiler.stop_trace()
                from . import xtrace
                t1 = time.perf_counter()
                rec["trace"] = xtrace.reduce(xtrace.load_dir(tmp))
                rec["trace_read_s"] = time.perf_counter() - t1
                shutil.rmtree(tmp, ignore_errors=True)


def memory_peak(devices):
    """Peak bytes in use on the fullest device, where the backend
    reports it (the CPU reports nothing)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices or ()]
    peaks = [int(p) for p in peaks if p is not None]
    return max(peaks) if peaks else None


def metric_values(metrics, rec) -> dict:
    out = {}
    for m in metrics:
        v = load_module("metrics", m["name"]).read(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result_line(rec: dict, spec: dict, trace: bool, device: dict) -> dict:
    metrics = metric_values(
        spec["per_layer"] if trace else spec["end_to_end"], rec)
    checks = rec["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": bool(correct), "attempted": int(rec["attempted"]),
           "failed": int(rec["failed"]), "metrics": metrics,
           "device": dict(device)}
    if trace and rec.get("trace"):
        t = rec["trace"]
        out["device"]["busy_s"] = t["busy_s"]
        out["device"]["window_s"] = t["window_s"]
        out["breakdown"] = {"device_ops": t["top_ops"][:10],
                            "idle_gaps": t["idle_gaps"][:10]}
    out["checks"] = checks
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # not used by the benchmark's own runs: the control that has to come
    # out not correct
    ap.add_argument("--control", default=None, help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def start_jax(chips: int):
    """Import JAX with the compilation cache in the checkout; raise
    ``NoChip`` unless at least ``chips`` TPU chips are attached."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"found {devices[0].platform}, not a TPU; "
                     f"there is no CPU fallback")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    return jax, devices


def run(args, t_start, jax=None, devices=None, spec=None) -> dict:
    """One run: returns the result line.  ``jax``/``devices`` are given
    by tests that drive a run without the chip."""
    spec = cell(args.workload) if spec is None else spec
    chips = int(spec["workload"]["chips"])
    if jax is None:
        jax, devices = start_jax(chips)
    cache0 = dir_bytes(CACHE_DIR)
    ctx = Context(args, spec, t_start, jax, devices[:chips])
    kind = load_module("kinds", ctx.mix["kind"])
    rec = kind.run(ctx)
    rec["setup_s"] = ctx.setup_s
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": rec.get("memory_peak_bytes")}
    if ctx.clock:
        s, w = ctx.marks.get("setup", {}), rec.get("window_compiles", {})
        print(f"[compile] set-up: {s.get('compiles')} compiles "
              f"({s.get('compile_s', 0):.3f}s), {s.get('cache_hits')} "
              f"persistent-cache hits, {s.get('traces')} traces; window: "
              f"{w.get('compiles')} compiles ({w.get('compile_s', 0):.3f}s), "
              f"{w.get('cache_hits')} cache hits, {w.get('traces')} traces",
              flush=True)
    print(f"[cache] {CACHE_DIR.name}: {dir_bytes(CACHE_DIR)} bytes after "
          f"the run, {cache0} before", flush=True)
    print(f"[memory] peak {device['memory_peak_bytes']} bytes on the "
          f"fullest chip", flush=True)
    print("[setup] " + ", ".join(f"{n} {s:.3f}s ({c} compiles)"
                                 for n, s, c in ctx.phases)
          + f"; total {ctx.setup_s:.3f}s", flush=True)
    for line in rec.get("notes", []):
        print(line, flush=True)
    out = result_line(rec, spec, ctx.trace, device)
    return out


def main(argv, t_start) -> int:
    args = parse(argv)
    try:
        out = run(args, t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
