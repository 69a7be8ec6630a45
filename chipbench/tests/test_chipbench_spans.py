"""Device-idle time by the program's spans (``lib/spans.py``) and the
readers built on it, on synthesised events and on the trace recorded on
a v5e chip."""
from pathlib import Path

import pytest

from chipbench.lib import harness, spans, xtrace

DATA = Path(__file__).parent / "data"
MS = 1_000_000
READERS = ("router.admit_idle_ms_per_query",
           "server.launch_idle_ms_per_batch",
           "server.finalize_idle_ms_per_batch",
           "mine.scan_host_idle_ms_per_job",
           "mine.rs_test_idle_ms_per_job",
           "mine.rebuild_idle_ms_per_job")

# busy [10, 35] and [60, 70] of a 100 ms window, as in
# test_chipbench_xtrace; the program's spans nest inside the
# benchmark's ``submit`` and ``collect``
OPS = {"/device:TPU:0": [("add", 10 * MS, 20 * MS),
                         ("mul", 25 * MS, 10 * MS),
                         ("add", 60 * MS, 10 * MS)]}
MODULES = {"/device:TPU:0": [("jit_f(12)", 10 * MS, 25 * MS),
                             ("jit_g(7)", 60 * MS, 10 * MS)]}
HOST = [("window", 0, 100 * MS), ("submit", 0, 10 * MS),
        ("cluster.submit", 0, 10 * MS),
        ("serving.fingerprint", 2 * MS, 4 * MS),
        ("collect", 35 * MS, 30 * MS), ("cluster.collect", 35 * MS, 30 * MS),
        ("cluster.fence", 40 * MS, 25 * MS),
        ("serving.finalize_rows", 40 * MS, 18 * MS),
        # two siblings share the idle gap [35, 60]
        ("serving.readback", 40 * MS, 5 * MS),
        ("serving.escalate", 45 * MS, 13 * MS),
        ("other", 70 * MS, 30 * MS),
        # a program span after the window is neither cut in nor counted
        ("cluster.flush", 120 * MS, 5 * MS)]
TR = {"ops": OPS, "modules": MODULES, "host": HOST}
FENCE = "collect>cluster.collect>cluster.fence>serving.finalize_rows"


def test_xtrace_keys_unchanged_by_program_spans():
    r = xtrace.reduce(TR)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["programs"] == pytest.approx({"jit_f": 0.025, "jit_g": 0.01})
    assert r["top_ops"][0] == ["add", pytest.approx(0.03)]
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"submit": 0.01, "collect": 0.025, "(none)": 0.03})


def test_idle_split_exactly_at_span_edges():
    r = spans.reduce(TR)
    assert r["idle_by_path"] == pytest.approx({
        "submit>cluster.submit": 0.006,
        "submit>cluster.submit>serving.fingerprint": 0.004,
        "collect>cluster.collect": 0.005,
        FENCE + ">serving.readback": 0.005,
        FENCE + ">serving.escalate": 0.013,
        "collect>cluster.collect>cluster.fence": 0.002,
        "(none)": 0.03,
    })
    x = xtrace.reduce(TR)
    assert sum(r["idle_by_path"].values()) == pytest.approx(
        x["window_s"] - x["busy_s"])
    assert sum(r["idle_by_path"].values()) == pytest.approx(
        sum(v for _, v in x["idle_gaps"]))


def test_span_count_counts_events_starting_in_the_window():
    c = spans.reduce(TR)["span_count"]
    assert c == {"submit": 1, "cluster.submit": 1, "serving.fingerprint": 1,
                 "collect": 1, "cluster.collect": 1, "cluster.fence": 1,
                 "serving.finalize_rows": 1, "serving.readback": 1,
                 "serving.escalate": 1}


def test_idle_split_over_two_devices():
    tr = dict(TR, ops={"/device:TPU:0": OPS["/device:TPU:0"],
                       "/device:TPU:1": [("add", 0, 100 * MS)]})
    r = spans.reduce(tr)
    # the second device is never idle: each path halves
    assert r["idle_by_path"]["(none)"] == pytest.approx(0.015)
    assert sum(r["idle_by_path"].values()) == pytest.approx(
        sum(v for _, v in xtrace.reduce(tr)["idle_gaps"]))


def test_summary_shares():
    s = spans.summary(spans.reduce(TR)["idle_by_path"])
    # 0.065 s idle: 0.024 under a phase, 0.011 in the self time of
    # cluster.submit / cluster.collect, 0.030 outside any program span
    assert s["idle_s"] == pytest.approx(0.065)
    assert s["phase"] == pytest.approx(100 * 24 / 65)
    assert s["wrapper"] == pytest.approx(100 * 11 / 65)
    assert s["outside"] == pytest.approx(100 * 30 / 65)
    assert s["phase_in_scope"] == pytest.approx(100 * 24 / 35)
    line = spans.line(spans.reduce(TR), 0.1, {"candidates": 2.0})
    assert line.startswith("[spans] 0.065000s idle")
    assert "9 program span events (90.0/s)" not in line
    assert "7 program span events (70.0/s)" in line
    assert line.endswith("per job candidates 2.000")


def test_recorded_v5e_trace_idle_sums_to_the_gaps():
    tr = xtrace.load(str(DATA / "matmul.xplane.pb"))
    x = xtrace.reduce(tr, spans=("probe_step",))
    r = spans.reduce(tr, spans=("probe_step",))
    assert r["span_count"]["probe_step"] == 5
    assert sum(r["idle_by_path"].values()) == pytest.approx(
        x["window_s"] - x["busy_s"])
    assert set(r["idle_by_path"]) <= {"probe_step", "(none)"}


def _rec(paths, count, **kw):
    rec = {"trace": {"idle_by_path": paths, "span_count": count}}
    rec.update(kw)
    return rec


SERVE = {
    "submit>cluster.submit": 0.002,
    "submit>cluster.submit>serving.fingerprint": 0.004,
    "submit>cluster.submit>cluster.flush>serving.encode": 0.010,
    "poll>cluster.flush>serving.join": 0.006,
    FENCE + ">serving.escalate": 0.013,
    "collect>cluster.collect>cluster.fence": 0.002,
    "(none)": 0.5,
}
SERVE_COUNT = {"cluster.submit": 4, "cluster.flush": 2, "cluster.fence": 3,
               "serving.fingerprint": 4}
MINE = {
    "mine_rs>mining.mine>mining.wavefront>mining.pack": 0.010,
    "mine_rs>mining.mine>mining.wavefront>mining.scan": 0.004,
    "mine_rs>mining.mine>mining.wavefront>mining.aggregate": 0.006,
    "mine_rs>mining.mine>mining.wavefront>mining.children": 0.001,
    "mine_rs>mining.mine>mining.wavefront>mining.children"
    ">mining.canonical": 0.020,
    "mine_rs>mining.mine>mining.wavefront>mining.children"
    ">mining.parent": 0.030,
    "mine_rs>mining.mine>mining.wavefront>mining.children"
    ">mining.rebuild": 0.040,
    "mine_rs>mining.mine": 0.002,
}
MINE_COUNT = {"mining.pack": 3, "mining.scan": 5, "mining.aggregate": 5,
              "mining.children": 9, "mining.canonical": 9,
              "mining.parent": 12, "mining.rebuild": 4}


@pytest.mark.parametrize("name,rec,want", [
    ("router.admit_idle_ms_per_query",
     _rec(SERVE, SERVE_COUNT, counters={"queries": 4}), 6.0 / 4),
    ("server.launch_idle_ms_per_batch", _rec(SERVE, SERVE_COUNT), 16.0 / 2),
    ("server.finalize_idle_ms_per_batch", _rec(SERVE, SERVE_COUNT),
     15.0 / 3),
    ("mine.scan_host_idle_ms_per_job", _rec(MINE, MINE_COUNT, jobs=2),
     20.0 / 2),
    ("mine.rs_test_idle_ms_per_job", _rec(MINE, MINE_COUNT, jobs=2),
     51.0 / 2),
    ("mine.rebuild_idle_ms_per_job", _rec(MINE, MINE_COUNT, jobs=2),
     40.0 / 2),
])
def test_readers_on_a_synthetic_record(name, rec, want):
    assert harness.load_module("metrics", name).read(rec) == \
        pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_spans_trace_or_denominator(name):
    read = harness.load_module("metrics", name).read
    assert read({}) is None
    assert read({"jobs": 2, "counters": {"queries": 4}}) is None
    # the trace of a program without the spans: idle time, no phases
    assert read(_rec({"submit": 1.0, "mine_rs": 1.0, "(none)": 1.0},
                     {"submit": 3, "mine_rs": 2}, jobs=2,
                     counters={"queries": 4})) is None
    # spans but no denominator
    assert read(_rec(dict(SERVE, **MINE), dict(MINE_COUNT, cluster_submit=1),
                     jobs=0, counters={"queries": 0})) is None


def test_program_spans_reach_a_traced_run(tmp_path, monkeypatch):
    """A traced run of a tiny mining cell, taken with the harness's own
    profiler options: the trace it reduces holds every host phase of
    the miner, once per unit of work."""
    from chipbench.tests import tiny

    seen = {}
    reduce = xtrace.reduce

    def both(tr, *a, **k):
        seen.update(spans.reduce(tr))
        return reduce(tr, *a, **k)

    monkeypatch.setattr(xtrace, "reduce", both)
    out = tiny.run(tiny.config(tmp_path), "mine", trace=1)
    assert out["correct"]
    count = seen["span_count"]
    for name in ("mining.mine", "mining.wavefront", "mining.pack",
                 "mining.scan", "mining.aggregate", "mining.children",
                 "mining.canonical", "mining.parent", "mining.rebuild"):
        assert count.get(name, 0) > 0, name
    assert count["mining.scan"] == count["mining.aggregate"]
    assert count["mining.mine"] == count["mine_rs"]
