"""The trace reduction, on synthesised events and on a trace recorded on
a v5e chip (``data/matmul.xplane.pb``: five runs of one jitted 512x512
matmul, each inside a ``probe_step`` host annotation)."""
from pathlib import Path

import pytest

from chipbench.lib import xtrace

DATA = Path(__file__).parent / "data"
MS = 1_000_000


def test_busy_idle_programs_and_gaps_from_synthesised_events():
    tr = {
        "ops": {"/device:TPU:0": [("add", 10 * MS, 20 * MS),
                                  ("mul", 25 * MS, 10 * MS),
                                  ("add", 60 * MS, 10 * MS)]},
        "modules": {"/device:TPU:0": [("jit_f(12)", 10 * MS, 25 * MS),
                                      ("jit_g(7)", 60 * MS, 10 * MS)]},
        "host": [("window", 0, 100 * MS), ("submit", 0, 10 * MS),
                 ("collect", 35 * MS, 30 * MS), ("other", 70 * MS, 30 * MS)],
    }
    r = xtrace.reduce(tr)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.035)  # [10,35] and [60,70]
    assert r["programs"] == pytest.approx({"jit_f": 0.025, "jit_g": 0.01})
    assert r["top_ops"][0] == ["add", pytest.approx(0.03)]
    gaps = dict(r["idle_gaps"])
    # [0,10] in submit; [35,60] in collect; [70,100] in no benchmark span
    assert gaps == pytest.approx({"submit": 0.01, "collect": 0.025,
                                  "(none)": 0.03})


def test_events_outside_the_window_are_cut_off():
    tr = {"ops": {"/device:TPU:0": [("a", 0, 50 * MS)]},
          "modules": {"/device:TPU:0": [("jit_a(1)", 0, 50 * MS)]},
          "host": [("window", 40 * MS, 20 * MS)]}
    r = xtrace.reduce(tr)
    assert r["busy_s"] == pytest.approx(0.01)
    assert r["programs"] == pytest.approx({"jit_a": 0.01})


def test_no_device_events_reads_no_busy_time():
    r = xtrace.reduce({"ops": {}, "modules": {},
                       "host": [("window", 0, MS)]})
    assert r["busy_s"] == 0.0 and r["programs"] == {}


def test_recorded_v5e_trace():
    tr = xtrace.load(str(DATA / "matmul.xplane.pb"))
    assert list(tr["ops"]) == ["/device:TPU:0"]
    r = xtrace.reduce(tr, spans=("probe_step",))
    assert set(r["programs"]) == {"jit_f"}
    assert r["programs"]["jit_f"] == pytest.approx(5 * 1.84e-6, rel=0.01)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["top_ops"][0][0].startswith("%convolution_reduce_fusion")


def test_peaks_are_known_only_for_listed_chips():
    from chipbench.lib import peaks
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")
