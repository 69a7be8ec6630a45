"""The benchmark's drivers end to end on the CPU at tiny sizes, with the
harness's look for a chip skipped: a sound run is correct, and the
control and each fault the cell can have come out not correct."""
import pytest

from chipbench.tests import tiny


@pytest.fixture(scope="module")
def cfg(tmp_path_factory):
    return tiny.config(tmp_path_factory.mktemp("bank"))


def test_serve_sound_run_is_correct(cfg):
    out = tiny.run(cfg, "serve")
    assert out["correct"], out["checks"]
    assert out["attempted"] == 8 and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_p95_s", "serve_qps", "setup_s"}
    assert list(out)[-1] == "checks"


def test_serve_control_approx_tier_is_not_correct(cfg):
    out = tiny.run(cfg, "serve", control="approx")
    assert not out["correct"]
    assert out["checks"]["inexact"]["value"] == out["attempted"]


def test_serve_answer_altered_where_produced_is_not_correct(cfg,
                                                            monkeypatch):
    from repro.serving.server import PatternServer
    real = PatternServer.finalize_rows

    def flipped(self, flight):
        rows = real(self, flight)
        rows[:, 0] = ~rows[:, 0]
        return rows

    monkeypatch.setattr(PatternServer, "finalize_rows", flipped)
    out = tiny.run(cfg, "serve")
    assert not out["correct"]
    assert out["checks"]["wrong_cells"]["value"] > 0


def test_serve_half_the_batch_left_out_is_not_correct(cfg, monkeypatch):
    from repro.serving.server import PatternServer
    real = PatternServer.finalize_rows

    def half(self, flight):
        rows = real(self, flight)
        rows[len(rows) // 2:] = False
        return rows

    monkeypatch.setattr(PatternServer, "finalize_rows", half)
    out = tiny.run(cfg, "serve")
    assert not out["correct"]


def test_mine_sound_run_and_control(cfg):
    out = tiny.run(cfg, "mine")
    assert out["correct"], out["checks"]
    assert "mine_s" in out["metrics"]
    out = tiny.run(cfg, "mine", control="drop_one")
    assert not out["correct"]


def test_mine_support_altered_where_produced_is_not_correct(cfg,
                                                            monkeypatch):
    from repro.mining.driver import AcceleratedMiner
    real = AcceleratedMiner.mine_rs

    def altered(self, *a, **kw):
        res = real(self, *a, **kw)
        p = next(iter(res.patterns))
        res.patterns[p] += 1
        return res

    monkeypatch.setattr(AcceleratedMiner, "mine_rs", altered)
    out = tiny.run(cfg, "mine")
    assert not out["correct"]
    assert out["checks"]["wrong_supports"]["value"] == 1


def test_stream_sound_run_and_control(cfg):
    out = tiny.run(cfg, "stream")
    assert out["correct"], out["checks"]
    assert "stream_arrivals_per_s" in out["metrics"]
    out = tiny.run(cfg, "stream", control="stale")
    assert not out["correct"]


def test_stream_refresh_returning_state_unchanged_is_not_correct(
        cfg, monkeypatch):
    from repro.serving.streaming import StreamingBank
    first = {}

    def unchanged(self, full=False):
        return first.setdefault(id(self), self.frequent())

    monkeypatch.setattr(StreamingBank, "refresh", unchanged)
    out = tiny.run(cfg, "stream")
    assert not out["correct"]
